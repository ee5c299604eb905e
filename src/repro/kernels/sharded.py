"""Mesh-aware dispatch: the fused block-sparse attention kernel under
shard_map.

`pallas_call` has no GSPMD partitioning rule, so under a multi-device mesh
the fused kernel (and its custom-VJP backward kernels) would either fail or
run fully replicated on every device. This wrapper makes the kernel
mesh-native instead:

  - the kernel's leading B*KV grid axis is split back into (B, KV) so the
    shard boundary falls on meshable dims: batch shards over the data axes
    ('pod','data'), KV heads over 'model' when KV % |model| == 0, with a
    clean fallback to batch-only sharding otherwise
    (distributed.sharding.kernel_shard_axes);
  - when the mesh has a 'seq' axis and the pattern's column extent fits
    (distributed.sharding.kernel_seq_axis), Q row-blocks additionally shard
    over 'seq': the body halo-exchanges the K/V edge blocks with the two
    adjacent shards via `jax.lax.ppermute`, rebases the replicated BCSR
    tables into shard-local halo coordinates, and runs the same Pallas
    kernels over the local rows with global-coordinate offsets
    (DESIGN.md §10). Patterns too wide for the halo fall back LOUDLY to
    batch/KV sharding — correctness never depends on the pattern;
  - the BCSR + SparsityPlan tables replicate per shard (in_spec P()) — they
    index the full, unsharded sequence axis, and they are kilobytes;
  - the body flattens (B_loc, KV_loc) -> N_loc = B_loc*KV_loc shard-locally
    and calls the unchanged `fused_block_sparse_attention` custom_vjp, so
    `jax.grad` of the wrapped op differentiates straight through the
    shard_map: partial-eval splits it into a forward and a backward
    shard_map, and the custom-VJP residuals (q/k/v/tables/o/LSE) flow
    between them as shard-local values — no gather of the (N, G, S)
    log-sum-exp to the host program, no resharding of the backward. In seq
    mode the halo exchange is ordinary differentiable lax around the
    custom_vjp, so its transpose (reverse ppermute reducing the dK/dV halo
    contributions back onto the owning shard) falls out of AD.

Every grid cell is independent across N = B*KV (the tables are shared by
all batch entries and heads), so sharding the leading axis changes nothing
about the math: the sharded forward is bitwise-identical to the
single-device kernel on each shard's rows (tested).

check_vma=False for the same reason as distributed/collectives.py: the
replicated table inputs plus a custom_vjp body defeat shard_map's
varying-manual-axes checker.
"""
from __future__ import annotations

import warnings
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.distributed import runtime
from repro.distributed.sharding import (kernel_pspecs_from_axes,
                                        kernel_seq_axis, kernel_shard_axes)
from repro.kernels.block_sparse_attn import fused_block_sparse_attention
from repro.kernels.dispatch import default_interpret, sharded_body

# One shard_map-wrapped fused op per (mesh DESCRIPTOR, axes, static kernel
# config) — cached so repeated traces reuse the same callable (the
# custom_vjp identity under it stays stable, mirroring
# block_sparse_attn._fused_op). Keyed on a hashable mesh descriptor, NOT the
# live Mesh object: an lru_cache on the Mesh itself retained every mesh ever
# constructed (tests, serve restarts, remesh after fault recovery) forever,
# along with its device handles. Re-creating an identical mesh now hits the
# same entry (tested), and the cache is LRU-bounded as a backstop against
# descriptor churn.
_OP_CACHE: OrderedDict = OrderedDict()
_OP_CACHE_MAX = 64


def _mesh_key(mesh: Mesh):
    """Hashable identity of a mesh: axis names + shape + device ids."""
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def _op_cache_size() -> int:
    return len(_OP_CACHE)


def _sharded_op(mesh: Mesh, baxes, kv_ax, seq, block, causal, sliding_window,
                interpret, with_plan, config):
    key = (_mesh_key(mesh), baxes, kv_ax, seq, block, causal, sliding_window,
           interpret, with_plan, config)
    op = _OP_CACHE.get(key)
    if op is not None:
        _OP_CACHE.move_to_end(key)
        return op
    op = _build_sharded_op(mesh, baxes, kv_ax, seq, block, causal,
                           sliding_window, interpret, with_plan, config)
    _OP_CACHE[key] = op
    while len(_OP_CACHE) > _OP_CACHE_MAX:
        _OP_CACHE.popitem(last=False)
    return op


def _build_sharded_op(mesh, baxes, kv_ax, seq, block, causal, sliding_window,
                      interpret, with_plan, config):
    """`seq` is None (sequence unsharded, PR-3 behaviour) or a static
    (n_shards, halo_left, halo_right) triple in block units."""
    seq_ax = "seq" if seq is not None else None
    qspec, kvspec, rep = kernel_pspecs_from_axes(baxes, kv_ax, seq_ax)
    n_tables = 4 if with_plan else 2

    def body(q, k, v, col_idx, nvalid, *plan):
        with sharded_body():
            B, KV, G, S, hd = q.shape  # shard-LOCAL sizes
            row_idx, nvalid_t = plan if with_plan else (None, None)
            kw = dict(block=block, causal=causal,
                      sliding_window=sliding_window, interpret=interpret,
                      config=config)
            if seq is None:
                o = fused_block_sparse_attention(
                    q.reshape(B * KV, G, S, hd), k.reshape(B * KV, S, hd),
                    v.reshape(B * KV, S, hd), col_idx, nvalid,
                    row_idx=row_idx, nvalid_t=nvalid_t, **kw)
                return o.reshape(B, KV, G, S, hd)

            n_seq, h_l, h_r = seq
            W = S // block                       # local row-blocks
            M = h_l + W + h_r                    # local K/V storage blocks
            i = jax.lax.axis_index("seq").astype(jnp.int32)
            r0 = i * W                           # global block of local row 0

            def ring(x, shift):
                """shift=+1 receives the left neighbour's tensor (ring)."""
                perm = [(j, (j + shift) % n_seq) for j in range(n_seq)]
                return jax.lax.ppermute(x, "seq", perm)

            # halo exchange: the pattern bounds which K/V blocks any local
            # row can read, so only the adjacent shards' edge blocks move
            ks, vs = [k], [v]
            if h_l:
                ks.insert(0, ring(k[:, :, S - h_l * block:, :], +1))
                vs.insert(0, ring(v[:, :, S - h_l * block:, :], +1))
            if h_r:
                ks.append(ring(k[:, :, :h_r * block, :], -1))
                vs.append(ring(v[:, :, :h_r * block, :], -1))
            kh = jnp.concatenate(ks, axis=2) if len(ks) > 1 else k
            vh = jnp.concatenate(vs, axis=2) if len(vs) > 1 else v

            # rebase the replicated forward BCSR into halo-local storage
            # coordinates: storage block s holds global column-block
            # c = r0 - h_l + s (the extent check guarantees every valid
            # entry lands in [0, M); clamped padding is skipped by nvalid)
            K_pad = col_idx.shape[1]
            col_l = jax.lax.dynamic_slice(col_idx, (r0, jnp.int32(0)),
                                          (W, K_pad))
            nv_l = jax.lax.dynamic_slice(nvalid, (r0,), (W,))
            col_l = jnp.clip(col_l - (r0 - h_l), 0, M - 1).astype(jnp.int32)
            # global-coordinate offsets for the kernels' masks and the
            # Alg. 6 zero-correction: [row0, col0]
            offs = jnp.stack([r0, r0 - h_l]).astype(jnp.int32)

            if with_plan:
                ncb, KT = row_idx.shape
                # transposed tables for the local window: storage col s ->
                # global col (mod ncb: the ring wraps at the ends; wrapped
                # columns are never referenced by local rows, so their
                # entry count is 0 and their dK/dV stays zero)
                cg = (r0 - h_l + jnp.arange(M, dtype=jnp.int32)) % ncb
                rig = row_idx[cg]                       # (M, KT) global rows
                nvtg = nvalid_t[cg]
                tpos = jnp.arange(KT, dtype=jnp.int32)[None, :]
                valid = tpos < nvtg[:, None]
                # the valid prefix lists rows ascending, so the rows owned
                # by THIS shard are a contiguous run — locate and shift it
                # left with a gather instead of a compaction sort
                lo = jnp.sum(valid & (rig < r0), axis=1).astype(jnp.int32)
                cnt = jnp.sum(valid & (rig >= r0) & (rig < r0 + W),
                              axis=1).astype(jnp.int32)
                gat = jnp.minimum(lo[:, None] + tpos, KT - 1)
                ril = jnp.take_along_axis(rig, gat, axis=1) - r0
                plan_l = dict(row_idx=jnp.clip(ril, 0, W - 1), nvalid_t=cnt)
            else:
                # plan-less: build the LOCAL transposed tables here in the
                # forward, from the rebased col table, so the custom_vjp
                # takes the with_plan path per shard. Deliberately NOT the
                # under-jit bcsr_transpose-in-the-backward fallback: its
                # scatter+argsort inside the grad-of-shard_map body
                # miscompiles under jit on CPU SPMD (wrong dK/dV for a
                # subset of column-blocks at larger N; inserting a
                # debug-print "fixes" it), so the seq path sticks to this
                # comparison/cumsum construction — maskT via equality
                # against every storage block, ranks via cumsum. O(M*W*K)
                # bools, kilobytes.
                tposk = jnp.arange(K_pad, dtype=jnp.int32)[None, None, :]
                mm = col_l[None, :, :] == \
                    jnp.arange(M, dtype=jnp.int32)[:, None, None]
                mm &= tposk < nv_l[None, :, None]
                mm = mm.any(-1)                         # (M, W) maskT
                cs = jnp.cumsum(mm, axis=1)             # actives <= row
                tpos = jnp.arange(W, dtype=jnp.int32)
                ril = jnp.sum(cs[:, :, None] <= tpos[None, None, :],
                              axis=1).astype(jnp.int32)
                plan_l = dict(row_idx=jnp.clip(ril, 0, W - 1),
                              nvalid_t=mm.sum(1).astype(jnp.int32))

            o = fused_block_sparse_attention(
                q.reshape(B * KV, G, S, hd),
                kh.reshape(B * KV, M * block, hd),
                vh.reshape(B * KV, M * block, hd), col_l, nv_l,
                offsets=offs, seq_len=n_seq * S, **plan_l, **kw)
            return o.reshape(B, KV, G, S, hd)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(qspec, kvspec, kvspec) + (rep,) * n_tables,
                         out_specs=qspec, check_vma=False)


def sharded_fused_attention(mesh: Mesh, q, k, v, col_idx, nvalid, *, block,
                            causal=False, sliding_window=None, interpret=None,
                            row_idx=None, nvalid_t=None, halo=None,
                            config=None):
    """shard_map'd `fused_block_sparse_attention` over `mesh`.

    q (B, KV, G, S, hd); k, v (B, KV, S, hd) — batch and KV heads as
    separate leading axes (ops._split_heads layout); tables as in
    `fused_block_sparse_attention`; interpret=None resolves from the
    platform (kernels/dispatch.py). Returns (B, KV, G, S, hd).

    `config` is the autotuned dispatch.KernelConfig (or None for defaults);
    it is part of the op-cache key, so differently-tuned patterns build
    separate shard_map ops while identical configs share one.

    `halo` is the pattern's (left, right) column extent in block units
    (SparsityPlan stats["halo"]). When the mesh has a 'seq' axis and the
    halo fits the shard width (kernel_seq_axis), the sequence axis shards
    too: Q rows split over 'seq', K/V edge blocks halo-exchange via
    ppermute, tables rebase into shard-local coordinates. Too-wide
    patterns (or halo=None) fall back to batch/KV sharding with a loud
    warning — never a silent full-sequence exchange.

    Differentiable end-to-end: jax.grad flows through the shard_map into the
    custom-VJP Pallas backward kernels, each shard running its own dQ/dK/dV
    grids over its local rows (seq mode reduces the dK/dV halo
    contributions back with the reverse permute, via AD of the exchange).
    Raises when no mesh axis can shard the kernel — running it replicated
    on every device is never the intended dispatch; use the jnp path there.
    """
    B, KV, S = q.shape[0], q.shape[1], q.shape[3]
    baxes, kv_ax = kernel_shard_axes(mesh, B, KV)
    seq_ax, seq_reason = kernel_seq_axis(mesh, S // block, halo)
    seq = None
    if seq_ax is not None:
        n_seq = mesh.shape["seq"]
        seq = (int(n_seq), int(halo[0]), int(halo[1]))
    elif mesh.shape.get("seq", 1) > 1:
        if baxes is None and kv_ax is None:
            raise RuntimeError(
                f"sharded_fused_attention: mesh {dict(mesh.shape)} has a "
                f"'seq' axis but the kernel cannot seq-shard ({seq_reason}) "
                f"and no batch/KV axis divides either (batch={B}, "
                f"kv_heads={KV}). Narrow the pattern (or supply the "
                f"SparsityPlan halo), fix the divisibility, or use "
                f"kernel='jnp' (the GSPMD path).")
        if runtime.is_coordinator():
            # every process takes this SPMD branch together; on a
            # multi-host fleet one copy of the warning beats N identical
            # ones (the RuntimeErrors above stay per-process: a crash
            # should explain itself in every worker's log)
            warnings.warn(
                f"sharded_fused_attention: mesh {dict(mesh.shape)} has a "
                f"'seq' axis but the kernel falls back to batch/KV sharding "
                f"— {seq_reason}. The kernel work is replicated |seq|="
                f"{mesh.shape['seq']}x; narrow the pattern or drop the "
                f"'seq' axis.", stacklevel=2)
    if baxes is None and kv_ax is None and seq is None:
        raise RuntimeError(
            f"sharded_fused_attention: no mesh axis shards the kernel on "
            f"mesh {dict(mesh.shape)} — batch={B} is indivisible by the data "
            f"axes and kv_heads={KV} by 'model'. The shard_map would run the "
            f"full kernel replicated on every device; use kernel='jnp' (the "
            f"GSPMD path) or fix the batch/head divisibility.")
    op = _sharded_op(mesh, baxes, kv_ax, seq, int(block), bool(causal),
                     None if sliding_window is None else int(sliding_window),
                     default_interpret(interpret), row_idx is not None,
                     config)
    args = (q, k, v, col_idx, nvalid)
    if row_idx is not None:
        args += (row_idx, nvalid_t)
    return op(*args)
