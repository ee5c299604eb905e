"""jit'd public wrappers around the Pallas kernels.

`spion_attention_kernel(...)` is a drop-in for core.sparse_attention.
bcsr_attention with use_pallas semantics: handles GQA head grouping, BCSR
table clamping, and dispatches the single-pass fused flash-style kernel —
the ONLY production kernel path (DESIGN.md §15). The paper-faithful
3-kernel SDDMM -> sparse softmax -> SpMM pipeline was demoted to the
pure-jnp oracle in kernels/ref.py: it exists to check the fused kernel in
parity tests and to reproduce the Fig. 6 breakdown, not to serve traffic.

The fused path is differentiable (custom VJP with Pallas backward kernels,
see block_sparse_attn.py) — it is the path the sparse training phase runs
through.

Mesh-aware: under an active multi-device mesh (distributed.sharding.
current_mesh()) the fused path routes through the shard_map wrapper
(kernels/sharded.py) — batch shards over the data axes, KV heads over
'model' when divisible — so sparse training keeps the kernel on pods.
pallas_call has no GSPMD partitioning rule, so the only alternatives under
a mesh are the jnp BCSR path or silently replicated kernel work; the
latter fails loudly (block_sparse_attn guard).

interpret=None resolves from the platform: compiled by Mosaic on TPU, the
Pallas interpreter where no accelerator is attached (CPU CI) — the same
call sites work everywhere.

The jits here are keyed ONLY on the kernel statics (causal, sliding_window,
block, interpret, and the autotuned KernelConfig) — never on the whole
ModelConfig, so unrelated config changes (act_shard, bench sweeps, dtype
knobs) don't retrace the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_mesh
from repro.kernels.block_sparse_attn import fused_block_sparse_attention
from repro.kernels.dispatch import default_interpret


def _prep_tables(bcsr):
    col = jnp.maximum(bcsr.col_idx, 0).astype(jnp.int32)
    nvalid = bcsr.nvalid.astype(jnp.int32)
    return col, nvalid


def _split_heads(q, k, v):
    """(B,S,H,hd)x(B,S,KV,hd) -> q (B, KV, G, S, hd), k/v (B, KV, S, hd).

    B and KV stay separate leading axes so the sharded dispatch can put the
    shard boundary on meshable dims (batch over the data axes, KV heads over
    'model'); the kernels' flat B*KV leading axis is formed shard-locally
    (or in _flatten_bk for the single-shard path)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    return qh, kh, vh, (B, S, H, hd, KV, G)


def _flatten_bk(qh, kh, vh, dims):
    B, S, H, hd, KV, G = dims
    return (qh.reshape(B * KV, G, S, hd), kh.reshape(B * KV, S, hd),
            vh.reshape(B * KV, S, hd))


def _merge_heads(o, dims):
    """(B, KV, G, S, hd) -> (B, S, H, hd)."""
    B, S, H, hd, KV, G = dims
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block", "interpret", "config"))
def _dispatch(q, k, v, col, nvalid, row_idx, nvalid_t, *, causal,
              sliding_window, block, interpret, config):
    qh, kh, vh, dims = _split_heads(q, k, v)
    B, S, H, hd, KV, G = dims
    qf, kf, vf = _flatten_bk(qh, kh, vh, dims)
    o = fused_block_sparse_attention(qf, kf, vf, col, nvalid, block=block,
                                     causal=causal,
                                     sliding_window=sliding_window,
                                     interpret=interpret,
                                     row_idx=row_idx, nvalid_t=nvalid_t,
                                     config=config)
    return _merge_heads(o.reshape(B, KV, G, S, hd), dims)


@functools.partial(jax.jit, static_argnames=("mesh", "causal",
                                             "sliding_window", "block",
                                             "interpret", "halo", "config"))
def _dispatch_sharded(q, k, v, col, nvalid, row_idx, nvalid_t, *, mesh,
                      causal, sliding_window, block, interpret, halo, config):
    from repro.kernels.sharded import sharded_fused_attention
    qh, kh, vh, dims = _split_heads(q, k, v)
    o = sharded_fused_attention(mesh, qh, kh, vh, col, nvalid, block=block,
                                causal=causal, sliding_window=sliding_window,
                                interpret=interpret, row_idx=row_idx,
                                nvalid_t=nvalid_t, halo=halo, config=config)
    return _merge_heads(o, dims)


def spion_attention_kernel(cfg, q, k, v, bcsr, *, interpret=None,
                           row_idx=None, nvalid_t=None, halo=None,
                           config=None):
    """Pallas-kernel counterpart of core.sparse_attention.bcsr_attention.
    The result is differentiable (sparse backward kernels); the single-pass
    fused kernel is the only path here — the legacy 3-kernel pipeline lives
    on solely as the kernels/ref.py oracle.
    `row_idx`/`nvalid_t` are a SparsityPlan's precomputed transposed tables
    (width KT*); supplying them shrinks the dK/dV backward streaming width
    to the true pattern width and removes the per-step under-jit
    bcsr_transpose. `halo` is the plan's static (left, right) column extent
    in block units — it unlocks 'seq'-axis sharding under a
    sequence-parallel mesh (kernels/sharded.py). `config` is the autotuned
    dispatch.KernelConfig for this pattern (kernels/autotune.py) — a
    jit-static scheduling knob that never changes results.

    Under an active multi-device mesh the fused path runs through the
    shard_map wrapper."""
    col, nvalid = _prep_tables(bcsr)
    interp = default_interpret(interpret)
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return _dispatch_sharded(q, k, v, col, nvalid, row_idx, nvalid_t,
                                 mesh=mesh, causal=cfg.causal,
                                 sliding_window=cfg.sliding_window,
                                 block=bcsr.block, interpret=interp,
                                 halo=None if halo is None else
                                 (int(halo[0]), int(halo[1])),
                                 config=config)
    return _dispatch(q, k, v, col, nvalid, row_idx, nvalid_t,
                     causal=cfg.causal, sliding_window=cfg.sliding_window,
                     block=bcsr.block, interpret=interp, config=config)
