"""Fig. 6: breakdown of MHA operation times — dense GEMM/softmax/GEMM vs
sparse SDDMM/sparse-softmax/SpMM — plus the `train_step` mode that times
forward+backward now that the fused kernel has a sparse backward, the
`bwd` mode that separates the dQ vs dK/dV backward kernels and proves the
SparsityPlan shrinks the dK/dV grid to the true pattern width KT*, and the
`sharded` mode that runs the sparse train step on a 4-virtual-device
(data=2, model=2) mesh in a subprocess and records jnp-vs-shard_map-fused
rows — proving the mesh-aware dispatch keeps the Pallas kernel (and its
sparse backward) on multi-device meshes — and the `seqshard` mode doing
the same on a (seq=2, data=2) mesh for the sequence-parallel
halo-exchange dispatch (DESIGN.md §10).

CPU wall-times of the jitted jnp paths (the GPU numbers in the paper are
hardware-specific; the *structure* — softmax dominating dense MHA, every
sparse op beating its dense counterpart at 90%+ sparsity — is what this
reproduces). Derived column reports op-count ratios from §4.4.

`train_step_rows` is the honesty check the paper's headline demands: SPION
claims cheaper *training*, so the number that matters is fwd+bwd, not fwd.
It times (a) attention-level value_and_grad through the dense path, the jnp
BCSR path, and — on compiled backends (TPU Mosaic / GPU Triton) — the fused
Pallas kernel with its custom-VJP backward, and (b) one full optimizer
train step in the dense vs sparse phase via launch.steps.make_train_step.

All wall clocks go through benchmarks/timing.time_us (warmup discarded,
min-of-reps, block_until_ready around every rep).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.timing import time_us as _time  # noqa: F401 (shared hygiene)
from repro.configs import get_config
from repro.core.sparse_attention import bcsr_from_blockmask
from repro.kernels import ref as kref


def rows(out, L=1024, D=64, block=32, density=0.08):
    B, H = 2, 2
    N = B * H
    key = jax.random.key(0)
    q = jax.random.normal(key, (N, L, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (N, L, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (N, L, D))
    rng = np.random.default_rng(0)
    n = L // block
    mask = rng.random((n, n)) < density
    np.fill_diagonal(mask, True)
    bcsr = bcsr_from_blockmask(mask, block)
    col = jnp.maximum(bcsr.col_idx, 0)

    # dense pipeline
    gemm1 = jax.jit(lambda q, k: jnp.einsum("nqd,nkd->nqk", q, k) / np.sqrt(D))
    soft = jax.jit(lambda s: jax.nn.softmax(s, -1))
    gemm2 = jax.jit(lambda p, v: jnp.einsum("nqk,nkd->nqd", p, v))
    s_dense = gemm1(q, k)
    p_dense = soft(s_dense)
    t_gemm1 = _time(gemm1, q, k)
    t_soft = _time(soft, s_dense)
    t_gemm2 = _time(gemm2, p_dense, v)

    # sparse pipeline (jnp reference path of the kernels)
    sddmm = jax.jit(lambda q, k: kref.sddmm_ref(q, k, bcsr.col_idx, block=block))
    s_sp = sddmm(q, k)
    ssoft = jax.jit(lambda s: kref.sparse_softmax_ref(s, bcsr.col_idx,
                                                      block=block, seq_len=L))
    p_sp = ssoft(s_sp)
    spmm = jax.jit(lambda p, v: kref.spmm_ref(p, v, bcsr.col_idx))
    t_sddmm = _time(sddmm, q, k)
    t_ssoft = _time(ssoft, s_sp)
    t_spmm = _time(spmm, p_sp, v)

    out("mha.dense_gemm_qk_us", round(t_gemm1, 1), "")
    out("mha.dense_softmax_us", round(t_soft, 1), "")
    out("mha.dense_gemm_av_us", round(t_gemm2, 1), "")
    out("mha.sparse_sddmm_us", round(t_sddmm, 1),
        f"speedup={t_gemm1 / t_sddmm:.2f}x (paper: 2.55x image)")
    out("mha.sparse_softmax_us", round(t_ssoft, 1),
        f"speedup={t_soft / t_ssoft:.2f}x (paper: 42.4x image)")
    out("mha.sparse_spmm_us", round(t_spmm, 1),
        f"speedup={t_gemm2 / t_spmm:.2f}x (paper: 2.54x image)")
    tot_d = t_gemm1 + t_soft + t_gemm2
    tot_s = t_sddmm + t_ssoft + t_spmm
    out("mha.total_speedup", round(tot_d / tot_s, 2),
        f"density={density} dense={tot_d:.0f}us sparse={tot_s:.0f}us")


def _skewed_pattern_plan(L, block):
    """The ISSUE's skewed layer-wise pattern: layer 0 sliding-window (column
    population <= 2), layer 1 causal diagonal + global stripe at column
    nrb//2 (population nrb/2). KT* = nrb/2 < nrb, so the plan-built dK/dV
    grid is half the always-safe padded width."""
    from repro.core.sparse_attention import build_sparsity_plan
    n = L // block
    m0 = np.zeros((n, n), bool)
    for r in range(n):
        m0[r, max(r - 1, 0): r + 1] = True
    m1 = np.zeros((n, n), bool)
    np.fill_diagonal(m1, True)
    stripe = n // 2
    m1[stripe:, stripe] = True
    K = max(int(m.sum(axis=1).max()) for m in (m0, m1))
    tabs = [bcsr_from_blockmask(m, block, max_k=K) for m in (m0, m1)]
    col = np.stack([np.asarray(t.col_idx) for t in tabs])
    nv = np.stack([np.asarray(t.nvalid) for t in tabs])
    return build_sparsity_plan(col, nv, block), col, nv


def bwd_rows(out, L=256, block=16, smoke=False):
    """`bwd` mode: dQ vs dK/dV backward-kernel timings through the host-built
    SparsityPlan on the skewed synthetic pattern, asserting the dK/dV grid
    width equals the plan's KT* (not the always-safe nrb). The padded-width
    run (KT = nrb, what the under-jit bcsr_transpose fallback pays) is the
    before; the plan run (KT*) is the after."""
    from repro.core.sparse_attention import bcsr_transpose
    from repro.kernels.block_sparse_attn import (_fused_dkv, _fused_dq,
                                                 _fused_forward, _lane_pad)
    from repro.kernels.dispatch import default_interpret

    if smoke:
        L = 128
    n = L // block
    plan, col_st, nv_st = _skewed_pattern_plan(L, block)
    kt = plan.kt_star
    assert kt < n, f"skewed pattern must shrink the grid (KT*={kt}, nrb={n})"
    # the dK/dV pallas grid is (N, ncb, row_idx.shape[-1], G): width == KT*
    assert plan.tables["row_idx"].shape[-1] == kt, \
        "plan dK/dV grid width must equal KT*"
    out("bwd.dkv_grid_width", kt,
        f"== KT* (true max column population); padded fallback would be nrb={n}")

    N, G, hd = 2, 1, 32
    key = jax.random.key(0)
    q = jax.random.normal(key, (N, G, L, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (N, L, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (N, L, hd))
    # the kernel wrappers take lane-padded operands, as _fused_op passes them
    q, k, v = _lane_pad(q), _lane_pad(k), _lane_pad(v)
    # layer 1 (global stripe) is the interesting one: its own population is
    # what drives KT*
    col = jnp.maximum(jnp.asarray(col_st[1]), 0)
    nv = jnp.asarray(nv_st[1])
    kw = dict(block=block, causal=True, sliding_window=None,
              interpret=default_interpret(None), head_dim=hd)
    o, lse = _fused_forward(q, k, v, col, nv, **kw)
    do = jax.random.normal(jax.random.fold_in(key, 3), o.shape)
    delta = jnp.sum(do * o, -1, keepdims=True)

    t_dq = _time(jax.jit(lambda: _fused_dq(q, k, v, do, lse, delta, col, nv,
                                           **kw)))
    ri_pad, nvt_pad = bcsr_transpose(col, nv, ncb=n)          # KT = nrb
    ri_plan = plan.tables["row_idx"][1]
    nvt_plan = plan.tables["nvalid_t"][1]
    t_pad = _time(jax.jit(lambda: _fused_dkv(q, k, v, do, lse, delta,
                                             ri_pad, nvt_pad, **kw)))
    t_plan = _time(jax.jit(lambda: _fused_dkv(q, k, v, do, lse, delta,
                                              ri_plan, nvt_plan, **kw)))
    out("bwd.dq_us", round(t_dq, 1), f"row-block grid (N,G,nrb,K) nrb={n}")
    out("bwd.dkv_padded_us", round(t_pad, 1),
        f"grid (N,ncb,{n},G) — always-safe KT=nrb (per-step-transpose path)")
    out("bwd.dkv_plan_us", round(t_plan, 1),
        f"grid (N,ncb,{kt},G) — plan KT*; grid_shrink={n / kt:.2f}x "
        f"speedup={t_pad / t_plan:.2f}x")


# Child program for the `sharded` mode: jax locks the host device count at
# first init, so the 4-virtual-device mesh needs a fresh process (same
# pattern as tests/test_distributed.py). Sizes come in via SPION_BENCH_*.
_SHARDED_CHILD = r"""
import dataclasses, os, time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, spion_dryrun_tables
from repro.models.registry import build
from repro.optim import adamw_init

L = int(os.environ["SPION_BENCH_L"])
B = int(os.environ["SPION_BENCH_B"])
reps = int(os.environ["SPION_BENCH_REPS"])
mesh = make_mesh((2, 2), ("data", "model"))
cfg = get_config("spion-lra").reduced()
cfg = cfg.replace(num_heads=4, num_kv_heads=2, head_dim=16,
                  spion=dataclasses.replace(cfg.spion, block_size=16))
bundle = build(cfg)
params = jax.tree_util.tree_map(
    lambda x: x.astype(jnp.float32) if x.ndim >= 2 else x,
    bundle.init(jax.random.key(0)))
opt = adamw_init(params)
rng = np.random.default_rng(0)
raw = rng.integers(0, cfg.vocab_size, (B, L + 1))
batch = {"tokens": jnp.asarray(raw[:, :-1]), "labels": jnp.asarray(raw[:, 1:])}
tables = spion_dryrun_tables(cfg, L)

def timed(step):
    args = (params, opt, batch, jnp.int32(0), tables)
    jax.block_until_ready(step(*args)[2]["loss"])          # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(step(*args)[2]["loss"])
    return (time.perf_counter() - t0) / reps * 1e6

with mesh_context(mesh):
    auto_step = make_train_step(cfg, spion=True, sparse_kernel="auto")
    jaxpr = str(jax.make_jaxpr(auto_step)(params, opt, batch, jnp.int32(0),
                                          tables))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr, \
        "auto must resolve to the shard_map-fused kernel under the mesh"
    t_jnp = timed(jax.jit(make_train_step(cfg, spion=True,
                                          sparse_kernel="jnp")))
    t_fused = timed(jax.jit(auto_step))
print("ROW,sharded.auto_is_shard_map_fused,1,"
      "auto train-step jaxpr has shard_map+pallas_call (mesh data=2 model=2)")
print(f"ROW,sharded.train_step_jnp_us,{t_jnp:.1f},"
      "jnp BCSR gather path under GSPMD (4 virtual cpu devices)")
print(f"ROW,sharded.train_step_fused_us,{t_fused:.1f},"
      "shard_map-fused (Pallas interpreter on CPU: records the dispatch + "
      f"trajectory; TPU numbers are the speedup claim) jnp/fused="
      f"{t_jnp / t_fused:.2f}x")
"""


def _subprocess_rows(out, child, smoke):
    """Run a bench child on 4 fake host devices and collect its ROW lines
    (jax locks the device count at first init, so meshes that differ from
    the parent's need a fresh process)."""
    import os
    import pathlib
    import subprocess
    import sys

    root = str(pathlib.Path(__file__).resolve().parent.parent)
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": "src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "SPION_BENCH_L": "128" if smoke else "256",
           "SPION_BENCH_B": "4",
           "SPION_BENCH_REPS": "2" if smoke else "5"}
    r = subprocess.run([sys.executable, "-c", child],
                       capture_output=True, text=True, cwd=root, env=env,
                       timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{r.stderr[-2000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, value, derived = line.split(",", 3)
            out(name, float(value), derived)


def sharded_rows(out, smoke=False):
    """`sharded` mode: before/after train-step rows (jnp BCSR vs
    shard_map-fused) on a (data=2, model=2) virtual mesh. Runs in a
    subprocess because the fake device count must be set before jax
    initialises. On CPU the fused numbers go through the Pallas interpreter
    — the row pair documents the mesh dispatch and gives the trajectory a
    before/after anchor, not a CPU speedup claim."""
    _subprocess_rows(out, _SHARDED_CHILD, smoke)


# Child program for the `seqshard` mode: sparse train step on a
# (seq=2, data=2) virtual mesh — the sequence-parallel dispatch
# (DESIGN.md §10). Rows record the pattern halo, assert the ppermute halo
# exchange is in the step, and time the jnp path vs the seq-sharded fused
# kernel (Pallas interpreter on CPU: dispatch + trajectory anchor, not a
# CPU speedup claim).
_SEQSHARD_CHILD = r"""
import dataclasses, os, time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_seq_mesh
from repro.launch.steps import make_train_step, spion_dryrun_tables
from repro.models.registry import build
from repro.optim import adamw_init

L = int(os.environ["SPION_BENCH_L"])
B = int(os.environ["SPION_BENCH_B"])
reps = int(os.environ["SPION_BENCH_REPS"])
mesh = make_seq_mesh(2, 2)
cfg = get_config("spion-lra").reduced()
cfg = cfg.replace(num_heads=4, num_kv_heads=2, head_dim=16,
                  spion=dataclasses.replace(cfg.spion, block_size=16))
bundle = build(cfg)
params = jax.tree_util.tree_map(
    lambda x: x.astype(jnp.float32) if x.ndim >= 2 else x,
    bundle.init(jax.random.key(0)))
opt = adamw_init(params)
rng = np.random.default_rng(0)
raw = rng.integers(0, cfg.vocab_size, (B, L + 1))
batch = {"tokens": jnp.asarray(raw[:, :-1]), "labels": jnp.asarray(raw[:, 1:])}
# bounded-extent pattern: the near-diagonal flood-fill shape seq sharding
# targets (the default global verticals would fall back by design)
tables = spion_dryrun_tables(cfg, L, max_extent=2)
h_l, h_r = tables["halo"]

def timed(step):
    args = (params, opt, batch, jnp.int32(0), tables)
    jax.block_until_ready(step(*args)[2]["loss"])          # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(step(*args)[2]["loss"])
    return (time.perf_counter() - t0) / reps * 1e6

with mesh_context(mesh):
    auto_step = make_train_step(cfg, spion=True, sparse_kernel="auto",
                                halo=tables["halo"])
    jaxpr = str(jax.make_jaxpr(auto_step)(params, opt, batch, jnp.int32(0),
                                          tables))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr and \
        "ppermute" in jaxpr, \
        "auto must resolve to the seq-sharded fused kernel under the mesh"
    t_jnp = timed(jax.jit(make_train_step(cfg, spion=True,
                                          sparse_kernel="jnp")))
    t_fused = timed(jax.jit(auto_step))
print(f"ROW,seqshard.halo_blocks,{h_l + h_r},"
      f"pattern col extent (left={h_l} right={h_r}) in blocks — the halo "
      "each shard exchanges with its neighbours")
print("ROW,seqshard.auto_is_seq_sharded,1,"
      "auto train-step jaxpr has shard_map+pallas_call+ppermute "
      "(mesh seq=2 data=2)")
print(f"ROW,seqshard.train_step_jnp_us,{t_jnp:.1f},"
      "jnp BCSR gather path under GSPMD (4 virtual cpu devices)")
print(f"ROW,seqshard.train_step_fused_us,{t_fused:.1f},"
      "seq-sharded fused (Pallas interpreter on CPU: records the dispatch + "
      f"trajectory; TPU numbers are the speedup claim) jnp/fused="
      f"{t_jnp / t_fused:.2f}x")
"""


def seqshard_rows(out, smoke=False):
    """`seqshard` mode: sparse train step on a (seq=2, data=2) virtual mesh
    — records the pattern halo and the jnp vs seq-sharded-fused train-step
    rows (subprocess; proves "auto" engages the pattern-bounded halo
    exchange on sequence-parallel meshes)."""
    _subprocess_rows(out, _SEQSHARD_CHILD, smoke)


def train_step_rows(out, L=512, D=32, block=32, density=0.12, smoke=False):
    """fwd+bwd timings: the training-speed claim, not the inference one."""
    import dataclasses

    from repro.core.sparse_attention import bcsr_attention
    from repro.kernels.block_sparse_attn import fused_block_sparse_attention
    from repro.launch.steps import make_train_step, spion_dryrun_tables
    from repro.models.registry import build
    from repro.optim import adamw_init

    if smoke:
        L, D = 128, 16
    B, H, KV = 2, 2, 2
    cfg = get_config("spion-lra").reduced().replace(
        num_heads=H, num_kv_heads=KV, head_dim=D, causal=False)
    cfg = cfg.replace(spion=dataclasses.replace(cfg.spion, block_size=block))
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, L, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, KV, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, KV, D))
    n = L // block
    rng = np.random.default_rng(0)
    mask = rng.random((n, n)) < density
    np.fill_diagonal(mask, True)
    bcsr = bcsr_from_blockmask(mask, block)
    pos = jnp.arange(L)

    from repro.models import attention as A

    def dense_loss(q, k, v):
        return jnp.sum(A.dense_attention(cfg, q, k, v, pos, pos) ** 2)

    def sparse_jnp_loss(q, k, v):
        return jnp.sum(bcsr_attention(cfg, q, k, v, bcsr) ** 2)

    t_dense = _time(jax.jit(jax.value_and_grad(dense_loss, argnums=(0, 1, 2))),
                    q, k, v)
    t_sparse = _time(jax.jit(jax.value_and_grad(sparse_jnp_loss, argnums=(0, 1, 2))),
                     q, k, v)
    out("train_step.attn_dense_fwdbwd_us", round(t_dense, 1), "")
    out("train_step.attn_sparse_jnp_fwdbwd_us", round(t_sparse, 1),
        f"speedup={t_dense / t_sparse:.2f}x density={density}")

    from repro.kernels.dispatch import is_compiled_backend
    if is_compiled_backend():
        from repro.kernels.ops import _flatten_bk, _split_heads
        col = jnp.maximum(bcsr.col_idx, 0)
        qs, ks, vs, dims = _split_heads(q, k, v)
        qh, kh, vh = _flatten_bk(qs, ks, vs, dims)

        def fused_loss(q, k, v):
            o = fused_block_sparse_attention(q, k, v, col, bcsr.nvalid,
                                             block=block, causal=cfg.causal)
            return jnp.sum(o ** 2)

        t_fused = _time(jax.jit(jax.value_and_grad(fused_loss, argnums=(0, 1, 2))),
                        qh, kh, vh)
        out("train_step.attn_sparse_fused_fwdbwd_us", round(t_fused, 1),
            f"speedup={t_dense / t_fused:.2f}x (custom VJP Pallas bwd)")
    else:
        out("train_step.attn_sparse_fused_fwdbwd_us", 0,
            "skipped: non-compiled backend runs the Pallas interpreter "
            "(compiled lanes: TPU Mosaic, GPU Triton)")

    # SparsityPlan before/after (any backend; Pallas interpreter on CPU):
    # fused fwd+bwd where the backward either rebuilds the transposed tables
    # under jit at KT = nrb (before) or consumes the host-built plan tables
    # at KT* (after), on the skewed sliding-window + global-stripe pattern.
    Lp = 128 if smoke else 256
    blkp = 16
    plan, col_st, nv_st = _skewed_pattern_plan(Lp, blkp)
    nrb_p = Lp // blkp
    key = jax.random.key(7)
    Np, Gp, hdp = 2, 1, 32
    qp = jax.random.normal(key, (Np, Gp, Lp, hdp))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (Np, Lp, hdp))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (Np, Lp, hdp))
    colp = jnp.maximum(plan.tables["col_idx"][1], 0)
    nvp = plan.tables["nvalid"][1]

    def loss_transpose(q, k, v):
        o = fused_block_sparse_attention(q, k, v, colp, nvp, block=blkp,
                                         causal=True)
        return jnp.sum(o ** 2)

    def loss_plan(q, k, v):
        o = fused_block_sparse_attention(
            q, k, v, colp, nvp, block=blkp, causal=True,
            row_idx=plan.tables["row_idx"][1],
            nvalid_t=plan.tables["nvalid_t"][1])
        return jnp.sum(o ** 2)

    reps = 3 if smoke else 5
    t_before = _time(jax.jit(jax.value_and_grad(loss_transpose,
                                                argnums=(0, 1, 2))),
                     qp, kp, vp, reps=reps)
    t_after = _time(jax.jit(jax.value_and_grad(loss_plan, argnums=(0, 1, 2))),
                    qp, kp, vp, reps=reps)
    out("train_step.attn_fused_bwd_transpose_us", round(t_before, 1),
        f"before: under-jit bcsr_transpose, dK/dV grid width nrb={nrb_p}")
    out("train_step.attn_fused_bwd_plan_us", round(t_after, 1),
        f"after: SparsityPlan, dK/dV grid width KT*={plan.kt_star} "
        f"speedup={t_before / t_after:.2f}x")

    # full optimizer step: dense phase vs sparse phase (jnp kernel — the
    # phase switch itself is what's being costed on CPU)
    bundle = build(cfg)
    params = bundle.init(jax.random.key(1))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.ndim >= 2 else x, params)
    opt = adamw_init(params)
    raw = rng.integers(0, cfg.vocab_size, (B, L + 1))
    batch = {"tokens": jnp.asarray(raw[:, :-1]), "labels": jnp.asarray(raw[:, 1:])}
    tables = spion_dryrun_tables(cfg, L)
    dense_step = jax.jit(make_train_step(cfg))
    sparse_step = jax.jit(make_train_step(cfg, spion=True, sparse_kernel="jnp"))
    reps = 2 if smoke else 5
    td = _time(lambda p, o, b: dense_step(p, o, b, jnp.int32(0))[2]["loss"],
               params, opt, batch, reps=reps)
    ts = _time(lambda p, o, b: sparse_step(p, o, b, jnp.int32(0), tables)[2]["loss"],
               params, opt, batch, reps=reps)
    out("train_step.model_dense_us", round(td, 1), "")
    out("train_step.model_sparse_us", round(ts, 1),
        f"speedup={td / ts:.2f}x seq={L} reduced-arch")


def serve_rows(out, smoke=False):
    """`serve` mode: the train->serve story in numbers.

    (a) continuous-batching engine throughput: fused-prefill tokens/s and
        batched decode tokens/s on a reduced arch;
    (b) the sparse-decode claim: jitted decode_step dense vs sparse
        (pattern-bounded cache-block gather) at S_cache in {1k, 4k} — the
        gather reads K*block positions instead of the whole cache, so the
        win must GROW with cache length and show at >= 4k even on CPU.
    """
    from repro.configs import get_config
    from repro.core.attention_exec import SparseAttentionExec
    from repro.launch.serve import Request, ServeEngine
    from repro.launch.steps import make_serve_step
    from repro.models.registry import build

    cfg = get_config("qwen2-7b").reduced().replace(remat=False)
    bundle = build(cfg)
    params = bundle.init(jax.random.key(0))
    rng = np.random.default_rng(0)

    # (a) engine throughput: prefill, then pure decode ticks
    P, max_new, slots = 64, 8, 4
    eng = ServeEngine(cfg, params, slots=slots, max_len=256)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, P).astype(np.int32),
                    max_new=max_new) for i in range(slots)]
    for r in reqs:
        eng.submit(r)
    eng.step()                                    # warm-up: prefill + 1 tick
    n0 = sum(len(r.out) for r in reqs)
    t0 = time.perf_counter()
    eng.run([])                                   # drain remaining decode ticks
    dt_dec = time.perf_counter() - t0
    gen = sum(len(r.out) for r in reqs) - n0      # tokens in the timed window
    eng2 = ServeEngine(cfg, params, slots=1, max_len=256)
    warm = rng.integers(0, cfg.vocab_size, P).astype(np.int32)
    eng2.run([Request(rid=0, prompt=warm, max_new=1)])       # compile prefill
    t0 = time.perf_counter()
    eng2.run([Request(rid=1, prompt=warm.copy(), max_new=1)])
    dt_pref = time.perf_counter() - t0
    out("serve.prefill_tok_s", round(P / max(dt_pref, 1e-9), 1),
        f"fused prefill, P={P}")
    out("serve.engine_decode_tok_s", round(gen / max(dt_dec, 1e-9), 1),
        f"{slots} slots, per-slot positions")

    # (b) dense vs sparse decode at growing cache lengths. Donate the cache
    # exactly as the engine's jitted decode does — without donation every
    # call pays a full functional cache copy that is identical for both
    # paths and drowns the read-less-cache signal this row exists to show.
    # min-of-reps timing: robust to noisy-neighbour CPU on CI runners.
    block, width, B = 32, 8, 4
    dense_step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    sparse_step = jax.jit(make_serve_step(cfg, spion=True),
                          donate_argnums=(1,))
    reps = 5 if smoke else 20

    def timed_decode(step, S, *extra):
        cache = bundle.init_cache(B, S)
        tok = jnp.zeros((B, 1), jnp.int32)
        pos = jnp.full((B,), S - 1, jnp.int32)    # full-cache worst case
        logits, cache = step(params, cache, tok, pos, *extra)   # compile
        jax.block_until_ready(logits)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            logits, cache = step(params, cache, tok, pos, *extra)
            jax.block_until_ready(logits)
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    speedups = {}
    for S in (1024, 4096):
        from repro.launch.steps import causal_band_tables
        tabs = causal_band_tables(cfg.num_layers, S // block, width=width)
        ex = SparseAttentionExec(
            {k: jnp.asarray(v) for k, v in tabs.items()},
            block=block, phase="decode")
        td = timed_decode(dense_step, S)
        ts = timed_decode(sparse_step, S, ex)
        tag = f"{S // 1024}k"
        speedups[S] = td / ts
        out(f"serve.decode_dense_us_{tag}", round(td, 1), f"S_cache={S}")
        out(f"serve.decode_sparse_us_{tag}", round(ts, 1),
            f"speedup={td / ts:.2f}x K*block={width * block} of {S}")
    out("serve.decode_sparse_speedup_4k", round(speedups[4096], 2),
        f"vs {speedups[1024]:.2f}x at 1k — the win grows with S_cache")

    # (c) the paged-cache claim (DESIGN.md §14): decode throughput paged vs
    # contiguous at matched slot counts, pool-vs-contiguous memory at 64
    # slots (the paged pool sizes to the WORST-CASE PAGE BUDGET of the
    # actual requests, not slots*max_len — that accounting gap is what lets
    # the paged engine run 64 concurrent slots where a contiguous cache
    # would allocate the full rectangle), and prefix-sharing telemetry on a
    # shared-system-prompt workload.
    P2, new2, SL = 16, 8, 256

    def engine_decode_tok_s(paged, slots, **kw):
        e = ServeEngine(cfg, params, slots=slots, max_len=SL, paged=paged,
                        **kw)
        rs = [Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab_size,
                                          P2).astype(np.int32),
                      max_new=new2) for i in range(slots)]
        for r in rs:
            e.submit(r)
        e.step()                      # admit-all + first tick (compile)
        n0 = sum(len(r.out) for r in rs)
        t0 = time.perf_counter()
        e.run([])
        dt = time.perf_counter() - t0
        return (sum(len(r.out) for r in rs) - n0) / max(dt, 1e-9), e

    tp16p, ep16 = engine_decode_tok_s(True, 16)
    tp16c, _ = engine_decode_tok_s(False, 16)
    out("serve.contig_decode_tok_s_16", round(tp16c, 1),
        f"16 slots, P={P2}, max_new={new2}")
    out("serve.paged_decode_tok_s_16", round(tp16p, 1),
        f"ratio={tp16p / max(tp16c, 1e-9):.2f}x vs contiguous, "
        f"page={ep16.page}")
    budget64 = 64 * -(-(P2 + new2) // ep16.page) + 1   # worst case + scratch
    tp64, e64 = engine_decode_tok_s(True, 64, num_pages=budget64)
    pool_b = e64.pool.nbytes
    # the contiguous rectangle the same 64 slots would have to allocate
    cdt = jnp.dtype(cfg.cache_dtype or cfg.dtype)
    contig_b = (2 * cfg.num_layers * 64 * SL * cfg.num_kv_heads
                * cfg.resolved_head_dim * cdt.itemsize)
    out("serve.paged_decode_tok_s_64", round(tp64, 1),
        f"64 slots from a {budget64}-page pool "
        f"({budget64 * ep16.page} positions vs contiguous {64 * SL})")
    out("serve.paged_pool_mib_64", round(pool_b / 2**20, 3),
        f"{budget64} pages x {ep16.page}")
    out("serve.contig_cache_mib_64", round(contig_b / 2**20, 3),
        f"64 slots x max_len={SL} rectangle")
    out("serve.paged_mem_ratio_64", round(contig_b / pool_b, 2),
        "contiguous bytes / pool bytes at 64 slots")

    # shared system prompt: 3 requests, 64-token common prefix
    sys_p = rng.integers(0, cfg.vocab_size, 64).astype(np.int32)
    esh = ServeEngine(cfg, params, slots=2, max_len=SL, paged=True)
    share = [Request(rid=i,
                     prompt=np.concatenate(
                         [sys_p, rng.integers(0, cfg.vocab_size,
                                              5).astype(np.int32)]),
                     max_new=4) for i in range(3)]
    esh.run(share)
    st = esh.prefix_stats
    out("serve.prefix_hit_rate", round(st["prefix_hit_rate"], 3),
        f"{st['hits']}/{st['lookups']} page lookups hit; "
        f"{st['prefix_tokens_reused']} prompt tokens reused")
    out("serve.prefix_prefill_fused_calls", st["prefill_fused"],
        "3 shared-prefix requests -> the prefix prefilled once")
