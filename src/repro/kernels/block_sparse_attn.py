"""Fused block-sparse flash attention — the BEYOND-PAPER kernel, now
differentiable end-to-end (jax.custom_vjp with Pallas forward AND backward)
and the ONLY production attention kernel (the paper's 3-kernel
SDDMM -> sparse softmax -> SpMM pipeline survives solely as the pure-jnp
oracle in kernels/ref.py — see DESIGN.md §15).

Forward: one kernel replaces the paper's three: for each
(batch*kv-head, q-head-in-group, row-block), the K active KV tiles stream
through VMEM with running (max, sum, acc) flash statistics. S^r and S^s
never touch HBM — this is the TPU-native realisation of the paper's
data-locality argument (DESIGN.md §2), and it removes the O(nnz * B^2)
intermediate traffic the faithful pipeline pays. The sparse softmax
zero-correction (Alg. 6 line 15) is applied to the final denominator, so
the kernel is bit-compatible (up to fp assoc.) with the reference
pipeline. Alongside the context it emits per-row log-sum-exp residuals
lse = m + log(denom); with the correction folded into denom, the softmax
probabilities reconstruct exactly as p = exp(s - lse) in the backward.

Double-buffered BCSR fetch (DESIGN.md §15): the gathered operands — K/V
tiles in the forward and dQ, Q/dO/lse/delta row slices in dK/dV — live in
HBM (`pltpu.HBM`) and are DMA'd into a `depth`-slot VMEM ring with
`pltpu.make_async_copy`, so the NEXT column block's fetch overlaps the
CURRENT block's matmul. Schedule per grid step (K = table width):
prologue starts DMAs 0..depth-2; loop iteration i first starts DMA
i+depth-1 into the slot iteration i-1 just drained, then waits DMA i
(slot i % depth) and computes. depth=1 degenerates to a synchronous
fetch; the depth (and the Mosaic grid semantics) come from the
`KernelConfig` the autotuner picked (kernels/autotune.py). In the
forward and dQ, entries past `nvalid` still fetch a (clamped, in-range)
tile up to the table width K and are masked out of the flash update as
exact no-ops. dK/dV instead stops at each column's own count: its loop,
prologue and prefetch are bounded by `nvalid_t[c]`, so a padded entry is
never fetched or computed, every DMA started is waited exactly once, and
a column no row block lists writes zero dK/dV.

Backward (flash-attention-2 style, sparse):
  dQ    — same (N, G, nrb) row-block grid as the forward, streaming the
          active KV tiles and accumulating dq = scale * sum_c ds_c K_c.
  dK/dV — column-block grid over the TRANSPOSED BCSR tables: for
          column-block c, stream the nvalid_t[c] row-blocks that reference
          it (and the G query heads sharing the kv head, innermost so the
          output tile is revisited consecutively) and accumulate
          dv += p^T dO, dk += scale * ds^T Q. The transposed tables come
          either from a host-built SparsityPlan (width KT* = true max column
          population, precomputed at phase transition) or, as a fallback,
          from the under-jit core.sparse_attention.bcsr_transpose at the
          always-safe width KT = nrb; the padded width sets only the table
          shape, never the work, so both give bit-identical dK/dV.
Both recompute p from (q, k, lse); ds = p * (dp - delta) with
delta = rowsum(dO * O). The Alg. 6 phantom positions carry constant score 0
and no value, so they alter only the forward normaliser — the standard
softmax cotangent identity still holds on the active pattern and gradients
match the dense reference there (tests/test_kernels.py).

Grids: fwd/dQ (N, G, nrb) with the K streaming loop INSIDE each grid step
(that is what makes the DMA ring possible); dK/dV (N, ncb, G) with the
nvalid_t[c] loop inside and g innermost-sequential for the scratch
accumulators.

Sequence-parallel operation (DESIGN.md §10): every kernel takes a third
scalar-prefetch input `offs = [row0, col0]` mapping shard-local block
indices to global ones (absolute row-block = local r + row0, absolute
column-block = storage col + col0). The causal / sliding-window tile masks
and the Alg. 6 zero-correction are computed in GLOBAL coordinates, so a
seq-shard running over its local Q rows and halo-extended K/V window gets
exactly the meshless math; the meshless path passes [0, 0] and is
bit-identical to before. `seq_len` (the non-causal zero-correction row
total) is overridable for the same reason — under a seq shard q.shape[2]
is the LOCAL row count, not the global sequence length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse_attention import bcsr_transpose
from repro.distributed.sharding import current_mesh
from repro.kernels.dispatch import (DEFAULT_CONFIG, KernelConfig,
                                    compiled_backend, default_interpret,
                                    in_sharded_body)

NEG = -1e30
LANES = 128


def _lane_pad(x):
    """Zero-pad the minor (head) axis to a multiple of 128 lanes. Mosaic
    DMAs whole lane tiles, so a narrower head dim cannot be sliced out of
    HBM; the zero columns add nothing to q.k^T or p.v. `_fused_op` owns
    this layout: it pads once per forward and once per backward, hands the
    padded operands to the three kernel wrappers, and slices the head dim
    back off their outputs."""
    pad = -x.shape[-1] % LANES
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _tile_mask(r, col, block, causal, sliding_window):
    """(block, block) validity of the (row-block r, col-block col) tile."""
    qpos = r * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    kpos = col * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ok = jnp.ones((block, block), bool)
    if causal:
        ok &= qpos >= kpos
    if sliding_window is not None:
        ok &= (qpos - kpos) < sliding_window
    return ok


def _depth(config, width):
    """Effective ring depth: never deeper than the streamed table width."""
    return max(1, min(int(config.depth), max(int(width), 1)))


def _compiler_params(config, interpret, default_semantics):
    """Mosaic lowering knobs from the tuned KernelConfig.

    None in interpret mode (nothing lowers) and off TPU. Mosaic gets
    dimension_semantics — config's for the fwd/dQ grids,
    `default_semantics` verbatim where the grid has mandatory-sequential
    dims (dK/dV's innermost g)."""
    if interpret or config is None or compiled_backend() != "tpu":
        return None
    sem = default_semantics
    if config.dimension_semantics is not None and \
            "arbitrary" not in default_semantics:
        rank = len(default_semantics)
        sem = tuple(config.dimension_semantics)[:rank]
        sem += ("arbitrary",) * (rank - len(sem))
    return pltpu.CompilerParams(dimension_semantics=sem)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(col_ref, nvalid_ref, off_ref, q_ref, k_hbm, v_hbm, o_ref,
                lse_ref, kbuf, vbuf, ksem, vsem, *, block, hd, K, depth,
                seq_len, scale, causal, sliding_window):
    n = pl.program_id(0)
    r = pl.program_id(2)
    nv = nvalid_ref[r]

    def kv_copies(slot, i):
        c = col_ref[r, i]
        src = pl.ds(c * block, block)
        return (pltpu.make_async_copy(k_hbm.at[n, src, :], kbuf.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[n, src, :], vbuf.at[slot],
                                      vsem.at[slot]))

    # prologue: fill the ring (depth-1 fetches in flight before compute)
    for j in range(min(depth - 1, K)):
        for cp in kv_copies(j, j):
            cp.start()

    q = q_ref[0, 0].astype(jnp.float32)          # (B, hd)

    # row statistics are (B, 1) columns, never 1-D vectors: Mosaic lays
    # out 2-D values only, and a column broadcasts across a (B, B) tile
    def step(i, carry):
        m_prev, l_prev, acc = carry
        ahead = i + depth - 1

        @pl.when(ahead < K)
        def _prefetch():
            # the slot iteration i-1 just drained (= ahead % depth)
            for cp in kv_copies(jax.lax.rem(ahead, depth), ahead):
                cp.start()

        slot = jax.lax.rem(i, depth)
        for cp in kv_copies(slot, i):
            cp.wait()
        k = kbuf[slot].astype(jnp.float32)       # (B, hd)
        v = vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(r + off_ref[0], col_ref[r, i] + off_ref[1], block,
                        causal, sliding_window)
        # entries past nvalid are fetched (uniform DMA schedule) but are
        # exact no-ops on the flash carry: s=NEG keeps m, alpha=exp(0)=1,
        # p=0 adds nothing to l or acc
        ok &= i < nv
        s = jnp.where(ok, s, NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                     # rescale factor
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m, l, acc = jax.lax.fori_loop(
        0, K, step, (jnp.full((block, 1), NEG, jnp.float32),
                     jnp.zeros((block, 1), jnp.float32),
                     jnp.zeros((block, hd), jnp.float32)))

    # Alg. 6 line 15 zero-correction: pruned positions count exp(0 - m).
    # Row positions are GLOBAL (off_ref[0] rebases seq-shard-local rows).
    rows = (r + off_ref[0]) * block + \
        jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    if causal:
        rt = (rows + 1).astype(jnp.float32)
        if sliding_window is not None:
            rt = jnp.minimum(rt, float(sliding_window))
    else:
        rt = jnp.full((block, 1), float(seq_len))
    # stored counts come from the same masks; recompute per active tile

    def count(i, acc_):
        ok = _tile_mask(r + off_ref[0], col_ref[r, i] + off_ref[1], block,
                        causal, sliding_window)
        ok &= i < nv
        return acc_ + jnp.sum(ok.astype(jnp.float32), -1, keepdims=True)

    stored = jax.lax.fori_loop(0, K, count,
                               jnp.zeros((block, 1), jnp.float32))
    denom = l + jnp.maximum(rt - stored, 0.0) * jnp.exp(-m)
    safe = jnp.where(denom == 0.0, 1.0, denom)
    o_ref[0, 0] = (acc / safe).astype(o_ref.dtype)
    # rows with truly empty denominators get lse=+inf -> p = 0 in bwd
    lse_ref[0, 0] = jnp.where(denom > 0.0, m + jnp.log(safe), jnp.inf)


def _zero_offsets():
    return jnp.zeros((2,), jnp.int32)


def _fused_forward(q, k, v, col_idx, nvalid, *, block, causal, sliding_window,
                   interpret, offsets=None, seq_len=None, config=None,
                   head_dim):
    """Returns (o (N, G, S, hd), lse (N, G, S, 1) fp32). q/k/v come
    lane-padded (`_lane_pad`) for Mosaic, and `head_dim` is the true head
    dim the softmax scale uses. `S` is the local row count; `seq_len`
    (default S) is the GLOBAL sequence length used by the non-causal
    zero-correction, and `offsets` the [row0, col0] rebasing of local block
    indices to global ones (see module docstring). lse
    carries a trailing unit axis so that its (block, 1) row slices meet
    Mosaic's tiling rule (second-minor dim a multiple of 8, minor dim the
    whole axis) at every block size."""
    N, G, S, hd = q.shape
    nrb, K = col_idx.shape
    config = DEFAULT_CONFIG if config is None else config
    depth = _depth(config, K)
    offsets = _zero_offsets() if offsets is None else offsets
    scale = 1.0 / np.sqrt(head_dim)
    kern = functools.partial(_fwd_kernel, block=block, hd=hd, K=K,
                             depth=depth,
                             seq_len=S if seq_len is None else int(seq_len),
                             scale=scale, causal=causal,
                             sliding_window=sliding_window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, G, nrb),
        in_specs=[
            pl.BlockSpec((1, 1, block, hd),
                         lambda n, g, r, col, nv, off: (n, g, r, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),   # K stays in HBM: DMA ring
            pl.BlockSpec(memory_space=pltpu.HBM),   # V stays in HBM: DMA ring
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, hd),
                         lambda n, g, r, col, nv, off: (n, g, r, 0)),
            pl.BlockSpec((1, 1, block, 1),
                         lambda n, g, r, col, nv, off: (n, g, r, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((depth, block, hd), k.dtype),    # K tile ring
            pltpu.VMEM((depth, block, hd), v.dtype),    # V tile ring
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    o, lse = pl.pallas_call(
        kern,
        name="fused_fwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, G, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((N, G, S, 1), jnp.float32)],
        compiler_params=_compiler_params(config, interpret,
                                         ("parallel",) * 3),
        interpret=interpret,
    )(col_idx, nvalid, offsets, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward: dQ  (row-block grid, streams active KV tiles — forward's twin)
# ---------------------------------------------------------------------------

def _dq_kernel(col_ref, nvalid_ref, off_ref, q_ref, k_hbm, v_hbm, do_ref,
               lse_ref, delta_ref, dq_ref, kbuf, vbuf, ksem, vsem, *, block,
               hd, K, depth, scale, causal, sliding_window):
    n = pl.program_id(0)
    r = pl.program_id(2)
    nv = nvalid_ref[r]

    def kv_copies(slot, i):
        c = col_ref[r, i]
        src = pl.ds(c * block, block)
        return (pltpu.make_async_copy(k_hbm.at[n, src, :], kbuf.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[n, src, :], vbuf.at[slot],
                                      vsem.at[slot]))

    for j in range(min(depth - 1, K)):
        for cp in kv_copies(j, j):
            cp.start()

    q = q_ref[0, 0].astype(jnp.float32)       # (B, hd)
    do = do_ref[0, 0].astype(jnp.float32)     # (B, hd)
    lse = lse_ref[0, 0]                       # (B, 1)
    delta = delta_ref[0, 0]                   # (B, 1)

    def step(i, acc):
        ahead = i + depth - 1

        @pl.when(ahead < K)
        def _prefetch():
            for cp in kv_copies(jax.lax.rem(ahead, depth), ahead):
                cp.start()

        slot = jax.lax.rem(i, depth)
        for cp in kv_copies(slot, i):
            cp.wait()
        k = kbuf[slot].astype(jnp.float32)    # (B, hd)
        v = vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(r + off_ref[0], col_ref[r, i] + off_ref[1], block,
                        causal, sliding_window)
        ok &= i < nv                                # padded entries: ds = 0
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    acc = jax.lax.fori_loop(0, K, step, jnp.zeros((block, hd), jnp.float32))
    dq_ref[0, 0] = acc.astype(dq_ref.dtype)


def _fused_dq(q, k, v, do, lse, delta, col_idx, nvalid, *, block, causal,
              sliding_window, interpret, offsets=None, config=None,
              head_dim):
    """dQ (N, G, S, hd) fp32 from lane-padded q/k/v/do (see _fused_forward)."""
    N, G, S, hd = q.shape
    nrb, K = col_idx.shape
    config = DEFAULT_CONFIG if config is None else config
    depth = _depth(config, K)
    offsets = _zero_offsets() if offsets is None else offsets
    scale = 1.0 / np.sqrt(head_dim)
    kern = functools.partial(_dq_kernel, block=block, hd=hd, K=K, depth=depth,
                             scale=scale, causal=causal,
                             sliding_window=sliding_window)
    qspec = pl.BlockSpec((1, 1, block, hd),
                         lambda n, g, r, col, nv, off: (n, g, r, 0))
    hbmspec = pl.BlockSpec(memory_space=pltpu.HBM)
    rowspec = pl.BlockSpec((1, 1, block, 1),
                           lambda n, g, r, col, nv, off: (n, g, r, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, G, nrb),
        in_specs=[qspec, hbmspec, hbmspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((depth, block, hd), k.dtype),
            pltpu.VMEM((depth, block, hd), v.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    return pl.pallas_call(
        kern,
        name="fused_dq",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, G, S, hd), jnp.float32),
        compiler_params=_compiler_params(config, interpret,
                                         ("parallel",) * 3),
        interpret=interpret,
    )(col_idx, nvalid, offsets, q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# backward: dK/dV  (column-block grid over the transposed BCSR tables)
# ---------------------------------------------------------------------------

def _dkv_kernel(row_ref, nvt_ref, off_ref, q_hbm, k_ref, v_ref, do_hbm,
                lse_hbm, delta_hbm, dk_ref, dv_ref, dk_acc, dv_acc, qbuf,
                dobuf, lsebuf, dltbuf, qsem, dosem, lsesem, dltsem, *, block,
                hd, KT, G, depth, scale, causal, sliding_window):
    n = pl.program_id(0)
    c = pl.program_id(1)
    g = pl.program_id(2)
    nvt = nvt_ref[c]

    def row_copies(slot, t):
        r = row_ref[c, t]
        src = pl.ds(r * block, block)
        return (pltpu.make_async_copy(q_hbm.at[n, g, src, :], qbuf.at[slot],
                                      qsem.at[slot]),
                pltpu.make_async_copy(do_hbm.at[n, g, src, :], dobuf.at[slot],
                                      dosem.at[slot]),
                pltpu.make_async_copy(lse_hbm.at[n, g, src, :],
                                      lsebuf.at[slot], lsesem.at[slot]),
                pltpu.make_async_copy(delta_hbm.at[n, g, src, :],
                                      dltbuf.at[slot], dltsem.at[slot]))

    @pl.when(g == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # the stream stops at this column's own count: padded entries past nvt
    # are never fetched, so every DMA started here is waited exactly once
    for j in range(min(depth - 1, KT)):
        @pl.when(j < nvt)
        def _prologue(j=j):
            for cp in row_copies(j, j):
                cp.start()

    k = k_ref[0].astype(jnp.float32)          # (B, hd) column block c
    v = v_ref[0].astype(jnp.float32)

    def step(t, carry):
        dk, dv = carry
        ahead = t + depth - 1

        @pl.when(ahead < nvt)
        def _prefetch():
            for cp in row_copies(jax.lax.rem(ahead, depth), ahead):
                cp.start()

        slot = jax.lax.rem(t, depth)
        for cp in row_copies(slot, t):
            cp.wait()
        r = row_ref[c, t]
        q = qbuf[slot].astype(jnp.float32)    # (B, hd) rows of block r
        do = dobuf[slot].astype(jnp.float32)
        lse = lsebuf[slot][:, :1]             # (B, 1)
        delta = dltbuf[slot][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _tile_mask(r + off_ref[0], c + off_ref[1], block, causal,
                        sliding_window)
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # contract the q-row axis: dv_c += p^T dO_r ; dk_c += scale ds^T Q_r
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, nvt, step, (jnp.zeros((block, hd), jnp.float32),
                      jnp.zeros((block, hd), jnp.float32)))
    dk_acc[...] += dk
    dv_acc[...] += dv

    @pl.when(g == G - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fused_dkv(q, k, v, do, lse, delta, row_idx, nvalid_t, *, block, causal,
               sliding_window, interpret, offsets=None, config=None,
               head_dim):
    """(dK, dV) (N, Sk, hd) fp32 from lane-padded q/k/v/do (see
    _fused_forward)."""
    N, G, S, hd = q.shape
    Sk = k.shape[1]
    ncb, KT = row_idx.shape
    config = DEFAULT_CONFIG if config is None else config
    depth = _depth(config, KT)
    offsets = _zero_offsets() if offsets is None else offsets
    scale = 1.0 / np.sqrt(head_dim)
    # lse/delta rows are DMA'd by gathered row-block, and a DMA moves whole
    # lane tiles: broadcast the (N, G, S, 1) columns across 128 lanes
    lse = jnp.broadcast_to(lse, (N, G, S, LANES))
    delta = jnp.broadcast_to(delta, (N, G, S, LANES))
    kern = functools.partial(_dkv_kernel, block=block, hd=hd, KT=KT, G=G,
                             depth=depth, scale=scale, causal=causal,
                             sliding_window=sliding_window)
    hbmspec = pl.BlockSpec(memory_space=pltpu.HBM)
    colspec = pl.BlockSpec((1, block, hd),
                           lambda n, c, g, row, nvt, off: (n, c, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # g innermost so every revisit of the (n, c) output tile is
        # consecutive (the scratch accumulators persist across g)
        grid=(N, ncb, G),
        in_specs=[hbmspec, colspec, colspec, hbmspec, hbmspec, hbmspec],
        out_specs=[colspec, colspec],
        scratch_shapes=[
            pltpu.VMEM((block, hd), jnp.float32),       # dk accumulator
            pltpu.VMEM((block, hd), jnp.float32),       # dv accumulator
            pltpu.VMEM((depth, block, hd), q.dtype),    # Q row-slice ring
            pltpu.VMEM((depth, block, hd), do.dtype),   # dO row-slice ring
            pltpu.VMEM((depth, block, LANES), jnp.float32),  # lse ring
            pltpu.VMEM((depth, block, LANES), jnp.float32),  # delta ring
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    dk, dv = pl.pallas_call(
        kern,
        name="fused_dkv",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, Sk, hd), jnp.float32),
                   jax.ShapeDtypeStruct((N, Sk, hd), jnp.float32)],
        compiler_params=_compiler_params(
            config, interpret, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(row_idx, nvalid_t, offsets, q, k, v, do, lse, delta)
    return dk, dv


# ---------------------------------------------------------------------------
# custom VJP assembly
# ---------------------------------------------------------------------------

def _int_zero(x):
    """float0 cotangent for integer-dtype primal inputs (the BCSR tables)."""
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _fused_op(block, causal, sliding_window, interpret, with_plan, seq_len,
              config):
    """One differentiable fused-attention op per static config (cached so the
    custom_vjp identity is stable across traces).

    with_plan=True takes precomputed transposed tables (row_idx, nvalid_t)
    as extra primal inputs — the host-built SparsityPlan path: the tables
    are row_idx.shape[1] = KT* (true max column population) wide and no
    bcsr_transpose runs under jit. with_plan=False is the fallback
    that rebuilds the transposed tables in every backward at width KT = nrb.

    `config` is the (hashable) KernelConfig the autotuner resolved — part
    of the cache key, so differently-tuned call sites get distinct compiled
    kernels while identical configs share one.

    Every op additionally takes the `offs = [row0, col0]` block-index
    rebasing as an int32 primal (float0 cotangent); seq_len=None means "use
    q.shape[2]" — both are [0,0]/None everywhere except inside a seq shard.

    The op is the one owner of the lane layout: the forward and the
    backward each lane-pad their operands once (`_lane_pad`) and slice the
    head dim back off, so residuals stay at the true head dim.
    """
    kw = dict(block=block, causal=causal, sliding_window=sliding_window,
              interpret=interpret, config=config)

    def fwd_(q, k, v, col_idx, nvalid, *, offsets):
        hd = q.shape[-1]
        o, lse = _fused_forward(_lane_pad(q), _lane_pad(k), _lane_pad(v),
                                col_idx, nvalid, offsets=offsets,
                                seq_len=seq_len, head_dim=hd, **kw)
        return o[..., :hd], lse

    def bwd_core(q, k, v, col_idx, nvalid, offs, o, lse, do, row_idx,
                 nvalid_t):
        """Shared backward body — both vjp variants differ only in where the
        transposed tables come from (plan residuals vs under-jit rebuild)."""
        hd = q.shape[-1]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                        keepdims=True)
        qp, kp, vp, dop = _lane_pad(q), _lane_pad(k), _lane_pad(v), \
            _lane_pad(do)
        dq = _fused_dq(qp, kp, vp, dop, lse, delta, col_idx, nvalid,
                       offsets=offs, head_dim=hd, **kw)
        dk, dv = _fused_dkv(qp, kp, vp, dop, lse, delta, row_idx, nvalid_t,
                            offsets=offs, head_dim=hd, **kw)
        return (dq[..., :hd].astype(q.dtype), dk[..., :hd].astype(k.dtype),
                dv[..., :hd].astype(v.dtype))

    if with_plan:
        @jax.custom_vjp
        def op(q, k, v, col_idx, nvalid, offs, row_idx, nvalid_t):
            return fwd_(q, k, v, col_idx, nvalid, offsets=offs)[0]

        def op_fwd(q, k, v, col_idx, nvalid, offs, row_idx, nvalid_t):
            o, lse = fwd_(q, k, v, col_idx, nvalid, offsets=offs)
            return o, (q, k, v, col_idx, nvalid, offs, row_idx, nvalid_t, o,
                       lse)

        def op_bwd(res, do):
            q, k, v, col_idx, nvalid, offs, row_idx, nvalid_t, o, lse = res
            dq, dk, dv = bwd_core(q, k, v, col_idx, nvalid, offs, o, lse, do,
                                  row_idx, nvalid_t)
            return (dq, dk, dv, _int_zero(col_idx), _int_zero(nvalid),
                    _int_zero(offs), _int_zero(row_idx), _int_zero(nvalid_t))

        op.defvjp(op_fwd, op_bwd)
        return op

    @jax.custom_vjp
    def op(q, k, v, col_idx, nvalid, offs):
        return fwd_(q, k, v, col_idx, nvalid, offsets=offs)[0]

    def op_fwd(q, k, v, col_idx, nvalid, offs):
        o, lse = fwd_(q, k, v, col_idx, nvalid, offsets=offs)
        return o, (q, k, v, col_idx, nvalid, offs, o, lse)

    def op_bwd(res, do):
        q, k, v, col_idx, nvalid, offs, o, lse = res
        row_idx, nvalid_t = bcsr_transpose(col_idx, nvalid,
                                           ncb=k.shape[1] // block)
        dq, dk, dv = bwd_core(q, k, v, col_idx, nvalid, offs, o, lse, do,
                              row_idx, nvalid_t)
        return dq, dk, dv, _int_zero(col_idx), _int_zero(nvalid), \
            _int_zero(offs)

    op.defvjp(op_fwd, op_bwd)
    return op


def fused_block_sparse_attention(q, k, v, col_idx, nvalid, *, block,
                                 causal=False, sliding_window=None,
                                 interpret=None, row_idx=None, nvalid_t=None,
                                 offsets=None, seq_len=None, config=None):
    """q (N, G, S, hd) — G query heads share each kv head; k, v (N, Sk, hd);
    col_idx (nrb, K) clamped, nvalid (nrb,). Returns (N, G, S, hd).

    Differentiable: jax.grad flows through Pallas dQ / dK/dV kernels (dK/dV
    sum over the G query heads of each kv head). `interpret=None` resolves
    from the platform (Mosaic on TPU, interpreter on CPU).

    `config` is a dispatch.KernelConfig — normally the one the autotuner
    cached for this pattern (kernels/autotune.py); None means the default
    double-buffered schedule. Configs change only scheduling, never
    results.

    When a host-built SparsityPlan supplies `row_idx (ncb, KT*)` and
    `nvalid_t (ncb,)`, no bcsr_transpose runs under jit. Without them the
    backward falls back to the under-jit transpose at the always-safe
    width KT = nrb. Either way the dK/dV backward streams only the
    nvalid_t[c] listed row blocks of each column block.

    Sequence-parallel callers (kernels/sharded.py seq mode) pass local
    tables, `offsets = [row0, col0]` (int32 (2,), the global block index of
    local Q row-block 0 and of K/V storage block 0) and the GLOBAL
    `seq_len`; Sk may then exceed S by the halo width. Meshless callers
    leave both at None (identical math to before).

    Single-shard op: under a multi-device mesh it must run inside the
    shard_map wrapper (kernels/sharded.py) — pallas_call has no GSPMD
    partitioning rule, so a bare call would be silently replicated on every
    device. That misuse fails loudly here instead.
    """
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1 and not in_sharded_body():
        raise RuntimeError(
            f"fused_block_sparse_attention called under a multi-device mesh "
            f"{dict(mesh.shape)} outside the shard_map wrapper: pallas_call "
            f"has no GSPMD partitioning rule, so the kernel would run fully "
            f"replicated on every device. Route the call through "
            f"kernels.ops.spion_attention_kernel (mesh-aware) or "
            f"kernels.sharded.sharded_fused_attention, or use the jnp BCSR "
            f"path (cfg.spion.kernel='jnp').")
    if config is not None and not isinstance(config, KernelConfig):
        raise TypeError(f"config must be a dispatch.KernelConfig or None, "
                        f"got {type(config).__name__}")
    op = _fused_op(int(block), bool(causal),
                   None if sliding_window is None else int(sliding_window),
                   default_interpret(interpret), row_idx is not None,
                   None if seq_len is None else int(seq_len),
                   DEFAULT_CONFIG if config is None else config)
    offs = _zero_offsets() if offsets is None else offsets.astype(jnp.int32)
    if row_idx is not None:
        return op(q, k, v, col_idx.astype(jnp.int32), nvalid.astype(jnp.int32),
                  offs, row_idx.astype(jnp.int32), nvalid_t.astype(jnp.int32))
    return op(q, k, v, col_idx.astype(jnp.int32), nvalid.astype(jnp.int32),
              offs)
