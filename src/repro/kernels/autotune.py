"""Fused-kernel autotuner with a persistent on-disk config cache.

The compiled kernel lane (DESIGN.md §15) has real tuning freedom — the DMA
pipeline depth of the BCSR-indexed K/V fetch and the Mosaic grid dimension
semantics — and the best point depends on
the sparsity pattern (how many column blocks a row streams), the tile
shape, the dtype and the backend. This module sweeps a bounded candidate
set per (pattern-digest, table-shape, dtype, backend), times each with a
warmup-discarded min-of-reps, and persists the winner as one small JSON
file per key under `SPION_AUTOTUNE_DIR` (default ~/.cache/spion/autotune).

The cache is consulted — a pure lookup, never a sweep — when a
`SparseAttentionExec` is constructed with concrete tables, so serving and
training hit tuned configs without retracing: the config rides the exec's
static pytree aux, and jit keys the trace on it exactly once.

Correctness contract: a config may only ever change SPEED. Every swept
candidate's output is checked bitwise against the default config's before
it is eligible to win, and a corrupted / stale / unparseable cache entry
falls back to the default config with a loud warning — never a crash,
never a silently different result (tests/test_autotune.py).

Keys: `pattern_digest` hashes the BCSR table payload (col_idx/nvalid and,
when plan-built, row_idx/nvalid_t) plus the block size, so two phases with
the same geometry but different patterns tune independently; the table
shape (nrb, K, block), dtype and backend name complete the filename. The
digest is the same notion of pattern identity as core.spion.plan_digest,
restricted to the kernel-visible arrays.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import (DEFAULT_CONFIG, KernelConfig,
                                    compiled_backend, default_interpret)

_VERSION = 1
_ENV_DIR = "SPION_AUTOTUNE_DIR"
_ENV_ENABLE = "SPION_AUTOTUNE"
# same key order as core.sparse_attention.PLAN_TABLE_KEYS (not imported to
# keep this module usable on bare tables dicts without the core package)
_TABLE_KEYS = ("col_idx", "nvalid", "row_idx", "nvalid_t")


def enabled() -> bool:
    return os.environ.get(_ENV_ENABLE, "1") not in ("0", "false", "off")


def cache_dir() -> str:
    return os.environ.get(
        _ENV_DIR, os.path.join(os.path.expanduser("~"), ".cache", "spion",
                               "autotune"))


def pattern_digest(tables, block) -> str:
    """sha256 over the kernel-visible table payload + block size."""
    h = hashlib.sha256()
    h.update(f"block={int(block)}".encode())
    for key in _TABLE_KEYS:
        val = tables.get(key) if hasattr(tables, "get") else None
        if val is None:
            continue
        arr = np.asarray(val)
        h.update(f"|{key}:{arr.dtype}:{arr.shape}:".encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _shape_sig(tables, block) -> str:
    col = np.asarray(tables["col_idx"])
    nrb, k = int(col.shape[-2]), int(col.shape[-1])
    return f"nrb{nrb}_k{k}_b{int(block)}"


def cache_path(digest: str, shape_sig: str, dtype, backend: str) -> str:
    name = f"{digest[:16]}__{shape_sig}__{jnp.dtype(dtype).name}__{backend}"
    return os.path.join(cache_dir(), name + ".json")


def _backend_name() -> str:
    return compiled_backend() or "interpret"


# ---------------------------------------------------------------------------
# cache IO (loud fallback on anything malformed)
# ---------------------------------------------------------------------------

def load_entry(path: str) -> dict | None:
    """Parse + validate one cache entry; None (with a warning) when bad."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            entry = json.load(f)
        if not isinstance(entry, dict):
            raise ValueError(f"entry is {type(entry).__name__}, not an object")
        if entry.get("version") != _VERSION:
            raise ValueError(f"cache version {entry.get('version')!r} != "
                             f"current {_VERSION} (stale entry)")
        entry["config"] = KernelConfig.from_json(entry["config"])
        return entry
    except (OSError, ValueError, TypeError, KeyError,
            json.JSONDecodeError) as e:
        warnings.warn(
            f"spion autotune: ignoring unusable cache entry {path} ({e}); "
            f"falling back to the default kernel config", stacklevel=2)
        return None


def lookup(tables, block, *, dtype=jnp.float32) -> KernelConfig | None:
    """Pure cache lookup (no sweep). None on miss / disabled / bad entry."""
    if not enabled():
        return None
    path = cache_path(pattern_digest(tables, block),
                      _shape_sig(tables, block), dtype, _backend_name())
    entry = load_entry(path)
    return None if entry is None else entry["config"]


def store(tables, block, config: KernelConfig, *, dtype=jnp.float32,
          best_us: float | None = None, swept: int = 0) -> str:
    path = cache_path(pattern_digest(tables, block),
                      _shape_sig(tables, block), dtype, _backend_name())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    entry = {"version": _VERSION, "backend": _backend_name(),
             "dtype": jnp.dtype(dtype).name,
             "shape_sig": _shape_sig(tables, block),
             "config": config.to_json(), "best_us": best_us, "swept": swept}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(entry, f, indent=1)
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# candidate sweep
# ---------------------------------------------------------------------------

def candidates(backend: str | None = None) -> list[KernelConfig]:
    """Bounded sweep set per backend (a handful, not a grid explosion)."""
    backend = _backend_name() if backend is None else backend
    if backend == "tpu":
        return [KernelConfig(depth=d, dimension_semantics=s)
                for d in (1, 2, 3)
                for s in (None, ("arbitrary", "arbitrary", "arbitrary"))]
    # other hosts sweep the pipeline depth only: the lane mechanics
    # (tune -> cache -> dispatch) must run end-to-end on CPU CI
    return [KernelConfig(depth=d) for d in (1, 2, 3)]


def _time_us(fn, *args, reps: int = 3, warmup: int = 1) -> float:
    """min-of-reps wall time in us; warmup iterations are discarded."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def tune(tables, block, *, heads: int = 1, group: int = 1, head_dim: int = 64,
         dtype=jnp.float32, causal: bool = False, sliding_window=None,
         reps: int = 3, interpret=None, write_cache: bool = True):
    """Sweep the candidate set on synthetic inputs shaped by the tables.

    Returns (best_config, report). The report lists every candidate's
    min-of-reps time and whether its output matched the default config's
    bitwise (mismatching candidates are disqualified — the cache must
    never change results). The winner is persisted unless
    write_cache=False."""
    from repro.kernels.block_sparse_attn import fused_block_sparse_attention

    col = jnp.maximum(jnp.asarray(tables["col_idx"]), 0).astype(jnp.int32)
    nvalid = jnp.asarray(tables["nvalid"]).astype(jnp.int32)
    if col.ndim == 3:        # stacked (Ly, nrb, K): tune on layer 0
        col, nvalid = col[0], nvalid[0]
    nrb = col.shape[0]
    ncb = max(nrb, int(np.asarray(col).max(initial=0)) + 1)
    interp = default_interpret(interpret)

    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (heads, group, nrb * block, head_dim), dtype)
    k = jax.random.normal(kk, (heads, ncb * block, head_dim), dtype)
    v = jax.random.normal(kv, (heads, ncb * block, head_dim), dtype)

    def run(config):
        fn = jax.jit(lambda q, k, v: fused_block_sparse_attention(
            q, k, v, col, nvalid, block=block, causal=causal,
            sliding_window=sliding_window, interpret=interp, config=config))
        return fn, np.asarray(fn(q, k, v))

    base_fn, base_out = run(DEFAULT_CONFIG)
    report = []
    best, best_us = DEFAULT_CONFIG, _time_us(base_fn, q, k, v, reps=reps)
    report.append({"config": DEFAULT_CONFIG, "us": best_us, "bitwise": True})
    for cand in candidates():
        if cand == DEFAULT_CONFIG:
            continue
        fn, out = run(cand)
        bitwise = bool(np.array_equal(out, base_out))
        us = _time_us(fn, q, k, v, reps=reps)
        report.append({"config": cand, "us": us, "bitwise": bitwise})
        if not bitwise:
            warnings.warn(
                f"spion autotune: candidate {cand} changed kernel output "
                f"bitwise — disqualified", stacklevel=2)
            continue
        if us < best_us:
            best, best_us = cand, us
    if write_cache:
        store(tables, block, best, dtype=dtype, best_us=best_us,
              swept=len(report))
    return best, report


def tune_plan(plan, **kw):
    """`tune` on a core.sparse_attention.SparsityPlan."""
    return tune(plan.tables, plan.tables["block"], **kw)


def describe(config: KernelConfig | None) -> str:
    if config is None:
        return "default"
    parts = [f"depth={config.depth}"]
    for f in dataclasses.fields(config):
        val = getattr(config, f.name)
        if f.name != "depth" and val is not None:
            parts.append(f"{f.name}={val}")
    return ",".join(parts)
