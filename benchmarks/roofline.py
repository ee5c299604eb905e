"""§Roofline reader + measured kernel roofline.

Two row families:

  rows()        aggregates artifacts/dryrun/*.json into the ANALYTIC
                roofline table (compute/memory/collective terms, dominant
                bottleneck). Run the dry-run first:
                PYTHONPATH=src python -m repro.launch.dryrun.
  kernel_rows() MEASURED %-of-roofline per fused kernel (fwd / dQ / dK,dV):
                times each Pallas kernel (benchmarks/timing hygiene),
                derives achieved FLOP/s from the analytic block-sparse op
                count, and reports it against the roofline ceiling at that
                kernel's operational intensity — min(peak_flops,
                OI * peak_bytes_s). Peaks come from a small per-backend
                table, overridable via SPION_PEAK_FLOPS / SPION_PEAK_BYTES_S
                (so a real TPU/GPU host can pin its datasheet numbers). On
                CPU the kernels run the Pallas interpreter: the percentages
                are tiny and NOT a performance claim — the rows exist so the
                compiled-lane trajectory has a per-kernel anchor CI can gate
                on (benchmarks/check_regression.py).
"""
from __future__ import annotations

import glob
import json
import os

ARTS = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

# (peak FLOP/s, peak bytes/s) per compiled-lane backend; "cpu" is a
# deliberately modest interpreter-host placeholder
_PEAKS = {"tpu": (275e12, 1.2e12), "gpu": (312e12, 2.0e12),
          "cpu": (1.0e11, 4.0e10)}


def _peaks():
    from repro.kernels.dispatch import compiled_backend
    backend = compiled_backend() or "cpu"
    flops, bw = _PEAKS[backend]
    return (backend,
            float(os.environ.get("SPION_PEAK_FLOPS", flops)),
            float(os.environ.get("SPION_PEAK_BYTES_S", bw)))


def kernel_rows(out, smoke=False):
    """Measured %-of-roofline for the fused fwd / dQ / dK,dV kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.timing import time_us
    from repro.core.sparse_attention import (bcsr_from_blockmask,
                                             bcsr_transpose)
    from repro.kernels.block_sparse_attn import (_fused_dkv, _fused_dq,
                                                 _fused_forward, _lane_pad)
    from repro.kernels.dispatch import default_interpret

    L, block, hd, N, G = (128, 16, 32, 2, 1) if smoke else (256, 32, 32, 2, 1)
    n = L // block
    rng = np.random.default_rng(0)
    mask = rng.random((n, n)) < 0.3
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, block)
    col, nv = jnp.maximum(b.col_idx, 0), b.nvalid
    nnzb = int(np.asarray(nv).sum())
    itemsize, NG = 4, N * G

    key = jax.random.key(0)
    q = jax.random.normal(key, (N, G, L, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (N, L, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (N, L, hd))
    # the kernel wrappers take lane-padded operands, as _fused_op passes them
    q, k, v = _lane_pad(q), _lane_pad(k), _lane_pad(v)
    kw = dict(block=block, causal=False, sliding_window=None,
              interpret=default_interpret(None), head_dim=hd)
    o, lse = _fused_forward(q, k, v, col, nv, **kw)
    do = jax.random.normal(jax.random.fold_in(key, 3), o.shape)
    delta = jnp.sum(do * o, -1, keepdims=True)
    ri, nvt = bcsr_transpose(col, nv, ncb=n)

    bb = block * block
    # analytic per-valid-block op/byte counts (fp32); the derived column
    # records the model so a reader can re-derive the percentages
    kernels = {
        "fused_fwd": (
            jax.jit(lambda: _fused_forward(q, k, v, col, nv, **kw)),
            NG * nnzb * (4 * bb * hd + 8 * bb),
            itemsize * (NG * (2 * L * hd + L) + NG * nnzb * 2 * block * hd)),
        "fused_dq": (
            jax.jit(lambda: _fused_dq(q, k, v, do, lse, delta, col, nv, **kw)),
            NG * nnzb * (6 * bb * hd + 10 * bb),
            itemsize * (NG * (3 * L * hd + 2 * L)
                        + NG * nnzb * 2 * block * hd)),
        "fused_dkv": (
            jax.jit(lambda: _fused_dkv(q, k, v, do, lse, delta, ri, nvt,
                                       **kw)),
            NG * nnzb * (8 * bb * hd + 10 * bb),
            itemsize * (N * 4 * L * hd
                        + NG * nnzb * (2 * block * hd + 2 * block))),
    }
    backend, peak_flops, peak_bw = _peaks()
    reps = 3 if smoke else 5
    for name, (fn, flops, nbytes) in kernels.items():
        us = time_us(fn, reps=reps)
        achieved = flops / (us * 1e-6)
        oi = flops / nbytes
        ceiling = min(peak_flops, oi * peak_bw)
        bound = "compute" if oi * peak_bw >= peak_flops else "memory"
        out(f"roofline.{name}.pct_of_peak",
            round(100.0 * achieved / ceiling, 4),
            f"{us:.1f}us {achieved / 1e9:.3f}GFLOP/s OI={oi:.1f}flop/B "
            f"{bound}-bound ceiling={ceiling / 1e9:.0f}GFLOP/s "
            f"backend={backend} nnzb={nnzb}"
            + (" (interpreter: trajectory anchor, not a perf claim)"
               if backend == "cpu" else ""))


def load_cells(mesh="single"):
    cells = []
    for p in sorted(glob.glob(os.path.join(ARTS, f"*__{mesh}__*.json"))):
        try:
            cells.append(json.load(open(p)))
        except Exception:
            pass
    return cells


def rows(out):
    cells = load_cells("single")
    if not cells:
        out("roofline.missing", 0, "run repro.launch.dryrun first")
        return
    ok = [c for c in cells if c.get("status") == "ok" and "roofline" in c]
    for c in ok:
        r = c["roofline"]
        t_exec = max(r.values())
        frac = {"t_compute": "compute", "t_memory": "memory",
                "t_collective": "collective"}[c["dominant"]]
        out(f"roofline.{c['arch']}.{c['shape']}.{c['mode']}",
            round(t_exec * 1e6, 1),
            f"bound={frac} tc={r['t_compute']*1e6:.0f}us tm={r['t_memory']*1e6:.0f}us "
            f"tcoll={r['t_collective']*1e6:.0f}us useful={c.get('useful_fraction') or 0:.2f} "
            f"mem={c['memory'].get('per_device_gb', float('nan')):.1f}GiB")
    sk = [c for c in cells if c.get("status") == "skipped"]
    out("roofline.cells_ok", len(ok), f"skipped={len(sk)} (documented)")
