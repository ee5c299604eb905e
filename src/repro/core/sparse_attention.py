"""Block-sparse (padded-BCSR) multi-head attention — the SPION sparse phase.

The sparsity pattern is a per-layer table:
    col_idx : (nrb, K) int32   active column-block ids per row-block, pad = -1
    nvalid  : (nrb,)   int32   number of valid entries per row (b_cnt / B)
K is the padded max-blocks-per-row; static => jit-able and load-balanced.

Semantics are the paper's (Alg. 5/6): S = softmax_P(QK^T/sqrt(hd)) V where the
softmax denominator counts pruned positions as exp(0 - max) each (Alg. 6
line 15: sum += exp(-max) * (L - b_cnt)). Causal archs (beyond-paper
extension) count only pruned *causal* positions.

Two executions:
  - `bcsr_attention` — pure-jnp gather path (CPU tests, GSPMD dry-run).
  - kernels/ops.py   — fused Pallas kernel (TPU target), same signature.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class BCSR(NamedTuple):
    col_idx: jnp.ndarray  # (nrb, K) int32, -1 padded
    nvalid: jnp.ndarray   # (nrb,) int32
    block: int            # B
    seq_len: int          # L


def bcsr_from_blockmask(mask: np.ndarray, block: int, max_k: int | None = None) -> BCSR:
    """Host-side: dense block mask (nrb, ncb) bool -> padded BCSR."""
    mask = np.asarray(mask, bool)
    nrb, ncb = mask.shape
    counts = mask.sum(axis=1)
    K = int(max_k if max_k is not None else max(int(counts.max()), 1))
    col = np.full((nrb, K), -1, np.int32)
    for r in range(nrb):
        idx = np.nonzero(mask[r])[0][:K]
        col[r, : len(idx)] = idx
    return BCSR(jnp.asarray(col), jnp.asarray(np.minimum(counts, K).astype(np.int32)),
                block, nrb * block)


def bcsr_transpose(col_idx, nvalid, ncb: int | None = None,
                   max_k: int | None = None):
    """Transpose a padded-BCSR table: (col_idx (nrb, K), nvalid (nrb,)) ->
    (row_idx (ncb, KT), nvalid_t (ncb,)).

    `row_idx[c]` lists, ascending, the row-blocks whose active set contains
    column-block `c`; entries past `nvalid_t[c]` are arbitrary in-range row
    ids (clamped padding, same convention the kernels use for `col_idx`).

    Pure jnp (gather/scatter/argsort) so it runs under jit on traced tables —
    the sparse-phase tables are step *inputs*, not compile-time constants
    (DESIGN.md §8). `max_k` bounds the padded width KT; it must be static.
    The default KT = nrb is the only always-safe bound: a vertical stripe
    (global-attention column) appears in every row-block.

    This is the FALLBACK path: when a host-built SparsityPlan supplies
    precomputed transposed tables (padded to the true width KT*), the fused
    VJP uses those instead and this never runs under jit.
    """
    col_idx = jnp.asarray(col_idx, jnp.int32)
    nvalid = jnp.asarray(nvalid, jnp.int32)
    nrb, K = col_idx.shape
    ncb = int(ncb) if ncb is not None else nrb
    valid = jnp.arange(K)[None, :] < nvalid[:, None]              # (nrb, K)
    # scatter into a dense block mask; invalid entries land in a spill column
    colc = jnp.where(valid, jnp.clip(col_idx, 0, ncb - 1), ncb)
    mask = jnp.zeros((nrb, ncb + 1), bool)
    mask = mask.at[jnp.arange(nrb)[:, None], colc].set(True)[:, :ncb]
    maskT = mask.T                                                # (ncb, nrb)
    KT = int(max_k) if max_k is not None else nrb
    # active rows first (ascending), inactive pushed to the back
    keys = jnp.where(maskT, jnp.arange(nrb)[None, :], nrb)
    row_idx = jnp.argsort(keys, axis=1)[:, :KT].astype(jnp.int32)
    nvalid_t = jnp.minimum(maskT.sum(axis=1), KT).astype(jnp.int32)
    return row_idx, nvalid_t


# the SparsityPlan's array payload — every site that threads tables through
# a step/scan filters on these keys (a missed key silently degrades the
# backward to the KT = nrb fallback, so keep the list in ONE place)
PLAN_TABLE_KEYS = ("col_idx", "nvalid", "row_idx", "nvalid_t")


class SparsityPlan(NamedTuple):
    """Host-built sparse-phase plan (DESIGN.md §8).

    `tables` is the step-input payload broadcast with the batch:
        col_idx  (Ly, nrb, K)   forward BCSR; entries past nvalid may be -1
                                (bcsr_from_blockmask convention — kernel
                                callers clamp, see ops._prep_tables)
        nvalid   (Ly, nrb)
        row_idx  (Ly, ncb, KT*) transposed BCSR; entries past nvalid_t are
                                clamped in-range row ids
        nvalid_t (Ly, ncb)
        block    int (static)
    `kt_star` is the TRUE max column population across all layers — the
    static padded width of the transposed tables (the fused VJP's dK/dV
    kernel streams only each column's nvalid_t entries of it).
    `stats` holds host-only occupancy numbers (never enters the jitted step).
    """
    tables: dict
    kt_star: int
    stats: dict


def host_transpose_tables(col_idx, nvalid, ncb: int | None = None,
                          max_kt: int | None = None):
    """Host-side (numpy) transpose of padded-BCSR tables, stacked or single.

    col_idx (Ly, nrb, K) / nvalid (Ly, nrb)  ->
        (row_idx (Ly, ncb, KT), nvalid_t (Ly, ncb), KT)
    with KT = the true max column population across layers (the tightest
    static width) unless `max_kt` pins it. Entries past `nvalid_t[l, c]` are
    clamped in-range row ids (same padding convention as `col_idx`), and the
    valid prefix lists row-blocks ascending — identical to the under-jit
    `bcsr_transpose` output on the valid region, but computed once at phase
    transition instead of inside every backward pass.
    """
    col = np.asarray(col_idx)
    nv = np.asarray(nvalid)
    squeeze = col.ndim == 2
    if squeeze:
        col, nv = col[None], nv[None]
    Ly, nrb, K = col.shape
    ncb = int(ncb) if ncb is not None else nrb
    # vectorized O(nnz log nnz) per layer — never materialises the dense
    # (nrb, ncb) block mask (nrb can reach ~8k at production seq lengths)
    counts = np.zeros((Ly, ncb), np.int64)
    entries = []
    for layer in range(Ly):
        rows, ks = np.nonzero(np.arange(K)[None, :] < nv[layer][:, None])
        cols = np.clip(col[layer, rows, ks], 0, ncb - 1).astype(np.int64)
        # dedupe (row, col) pairs — duplicate/clamped entries count once,
        # matching the dense-mask semantics of bcsr_transpose
        pairs = np.unique(rows.astype(np.int64) * ncb + cols)
        rows_u = (pairs // ncb).astype(np.int32)
        cols_u = (pairs % ncb).astype(np.int32)
        np.add.at(counts[layer], cols_u, 1)
        entries.append((rows_u, cols_u))
    KT = int(max_kt) if max_kt is not None else max(int(counts.max()), 1)
    row_idx = np.zeros((Ly, ncb, KT), np.int32)
    nvalid_t = np.minimum(counts, KT).astype(np.int32)
    for layer in range(Ly):
        rows_u, cols_u = entries[layer]
        order = np.lexsort((rows_u, cols_u))     # column-major, rows ascending
        rows_s, cols_s = rows_u[order], cols_u[order]
        starts = np.zeros(ncb + 1, np.int64)
        np.cumsum(counts[layer], out=starts[1:])
        pos = np.arange(len(rows_s)) - starts[cols_s]   # rank within column
        keep = pos < KT
        row_idx[layer, cols_s[keep], pos[keep]] = rows_s[keep]
        # clamped padding: repeat each column's last valid row id (0 if empty)
        nvt = nvalid_t[layer]
        fill = np.where(nvt > 0,
                        row_idx[layer, np.arange(ncb), np.maximum(nvt - 1, 0)],
                        0)
        tail = np.arange(KT)[None, :] >= nvt[:, None]
        row_idx[layer] = np.where(tail, fill[:, None], row_idx[layer])
    if squeeze:
        return row_idx[0], nvalid_t[0], KT
    return row_idx, nvalid_t, KT


def pattern_col_extents(col_idx, nvalid, *, ncb: int | None = None):
    """Host-side (numpy) per-layer column extents of a padded-BCSR pattern.

    col_idx (Ly, nrb, K) or (nrb, K) / nvalid (Ly, nrb) or (nrb,) ->
        (left (Ly,), right (Ly,)) int arrays, in BLOCK units:
        left[l]  = max over rows r of (r - min valid col of r), >= 0
        right[l] = max over rows r of (max valid col of r - r), >= 0

    These are computed from the RAW table entries — the tables alone decide
    which KV blocks the kernels ever touch; the causal / sliding-window tile
    masks only *remove* positions inside listed blocks, so the raw extent is
    an upper bound on every row-block's true column span regardless of the
    mask config (the property the halo-exchange scheme needs; causal
    patterns get right == 0 and sliding-window bands get left ~ window/B
    for free because the tables themselves are banded). Rows with no valid
    entries contribute 0."""
    col = np.asarray(col_idx, np.int64)
    nv = np.asarray(nvalid, np.int64)
    squeeze = col.ndim == 2
    if squeeze:
        col, nv = col[None], nv[None]
    Ly, nrb, K = col.shape
    ncb_ = int(ncb) if ncb is not None else nrb
    valid = np.arange(K)[None, None, :] < nv[:, :, None]          # (Ly,nrb,K)
    colc = np.clip(col, 0, ncb_ - 1)
    rows = np.arange(nrb)[None, :, None]
    left = np.where(valid, rows - colc, 0).max(axis=(1, 2))
    right = np.where(valid, colc - rows, 0).max(axis=(1, 2))
    left = np.maximum(left, 0).astype(np.int64)
    right = np.maximum(right, 0).astype(np.int64)
    if squeeze:
        return left[:1], right[:1]
    return left, right


def build_sparsity_plan(col_idx, nvalid, block: int, *, ncb: int | None = None,
                        max_kt: int | None = None) -> SparsityPlan:
    """Build the full SparsityPlan from (stacked or single-layer) forward
    BCSR tables. Pattern generation is a rare host-side event, so this runs
    in numpy; the products are cheap step inputs. Always returns stacked
    tables (single-layer inputs get Ly=1)."""
    col = np.asarray(col_idx, np.int32)
    nv = np.asarray(nvalid, np.int32)
    if col.ndim == 2:
        col, nv = col[None], nv[None]
    Ly, nrb, K = col.shape
    ncb_ = int(ncb) if ncb is not None else nrb
    row_idx, nvalid_t, kt = host_transpose_tables(col, nv, ncb=ncb_,
                                                  max_kt=max_kt)
    ext_l, ext_r = pattern_col_extents(col, nv, ncb=ncb_)
    stats = {
        "kt_star": int(kt),
        "nrb": int(nrb),
        "ncb": int(ncb_),
        "K": int(K),
        "per_layer_max_col_population": nvalid_t.max(axis=1).astype(int).tolist(),
        "per_layer_density": [round(float(d), 6)
                              for d in nv.sum(axis=1) / float(nrb * ncb_)],
        "dkv_grid_shrink": round(float(nrb) / float(kt), 4),
        # the share of the padded transposed table that the dK/dV kernel
        # skips: it streams only each column's nvalid_t listed row blocks
        "dkv_skipped_share": 1.0 - float(nvalid_t.sum()) / float(
            Ly * ncb_ * kt),
        # sequence-parallel halo bounds (DESIGN.md §10): the tables are one
        # stacked step input traced through the layer scan, so the shard-map
        # halo must cover every layer — the per-layer extents are kept for
        # diagnostics, the max is what the dispatch consumes
        "col_extent_left": ext_l.astype(int).tolist(),
        "col_extent_right": ext_r.astype(int).tolist(),
        # a list, not a tuple: plan_stats round-trips through checkpoint
        # JSON, which would silently turn a tuple into a list on resume
        "halo": [int(ext_l.max()), int(ext_r.max())],
    }
    tables = {
        "col_idx": jnp.asarray(col),
        "nvalid": jnp.asarray(nv),
        "row_idx": jnp.asarray(row_idx),
        "nvalid_t": jnp.asarray(nvalid_t),
        "block": int(block),
    }
    return SparsityPlan(tables, int(kt), stats)


def full_bcsr(seq_len: int, block: int) -> BCSR:
    """All-blocks-active BCSR (sparse path must equal dense attention)."""
    nrb = seq_len // block
    col = np.tile(np.arange(nrb, dtype=np.int32), (nrb, 1))
    return BCSR(jnp.asarray(col), jnp.full((nrb,), nrb, np.int32), block, seq_len)


def bcsr_attention(cfg, q, k, v, bcsr: BCSR, *, interpret_kernel=None,
                   row_chunk=None):
    """q (B,S,H,hd); k,v (B,S,KV,hd); returns (B,S,H,hd).

    Pure-jnp padded-BCSR attention with the paper's sparse-softmax
    zero-correction, chunked over row-blocks with per-chunk remat so the
    gathered block tensors are never all resident (the Pallas kernel is the
    TPU-native version; this path is its GSPMD-compatible stand-in).
    """
    nrb_total = q.shape[1] // bcsr.block
    rc = row_chunk or max(1, min(nrb_total, 2**21 // (bcsr.block * bcsr.block *
                                                      max(bcsr.col_idx.shape[1], 1))))
    if nrb_total and rc < nrb_total and nrb_total % rc == 0:
        nch = nrb_total // rc
        col = bcsr.col_idx.reshape(nch, rc, -1)
        nval = bcsr.nvalid.reshape(nch, rc)
        B_, _, H_, hd_ = q.shape
        qch = jnp.moveaxis(
            q.reshape(B_, nch, rc * bcsr.block, H_, hd_), 1, 0)
        roff = (jnp.arange(nch) * rc).astype(jnp.int32)

        @jax.checkpoint
        def one(args):
            qc, cc, nv, off = args
            return _bcsr_rows(cfg, qc, k, v,
                              BCSR(cc, nv, bcsr.block, bcsr.seq_len), off)

        # scan-with-unroll, not lax.map: see dense_attention (cost_analysis
        # counts a rolled body once)
        _, out = jax.lax.scan(lambda _, x: (None, one(x)), None,
                              (qch, col, nval, roff),
                              unroll=min(cfg.scan_unroll, nch))
        return jnp.moveaxis(out, 0, 1).reshape(q.shape)
    return _bcsr_rows(cfg, q, k, v, bcsr, jnp.int32(0))


def _bcsr_rows(cfg, q, k, v, bcsr: BCSR, row_offset):
    """BCSR attention for the row-blocks covered by q (absolute row-block
    index of q's first block = row_offset)."""
    B, Sq, H, hd = q.shape
    L = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    Bb = bcsr.block
    nrb = Sq // Bb          # row-blocks in THIS chunk
    K = bcsr.col_idx.shape[1]
    col = bcsr.col_idx      # (nrb, K)
    colc = jnp.maximum(col, 0)

    qb = q.reshape(B, nrb, Bb, KV, G, hd)
    kb = k.reshape(B, L // Bb, Bb, KV, hd)
    vb = v.reshape(B, L // Bb, Bb, KV, hd)
    # gather active key/value blocks per row-block: (B, nrb, K, Bb, KV, hd)
    kg = kb[:, colc]
    vg = vb[:, colc]

    # scores: (B, KV, G, nrb, Bb, K, Bb)
    s = jnp.einsum("brpkgh,brcqkh->bkgrpcq", qb, kg).astype(jnp.float32)
    s = s / np.sqrt(hd)

    # masks: padded blocks, causal / sliding-window within active blocks
    abs_rows = (row_offset + jnp.arange(nrb)) * Bb
    qpos = abs_rows[:, None, None, None] + jnp.arange(Bb)[None, :, None, None]
    kpos = (colc * Bb)[:, None, :, None] + jnp.arange(Bb)[None, None, None, :]
    ok = (col >= 0)[:, None, :, None]
    if cfg.causal:
        ok = ok & (qpos >= kpos)
    if cfg.sliding_window:
        ok = ok & (qpos - kpos < cfg.sliding_window)
    s = jnp.where(ok[None, None, None], s, -jnp.inf)

    sflat = s.reshape(B, KV, G, nrb, Bb, K * Bb)
    mx = jnp.max(sflat, axis=-1, keepdims=True)
    mx = jnp.maximum(mx, -1e30)  # rows with nothing active
    ex = jnp.where(jnp.isneginf(sflat), 0.0, jnp.exp(sflat - mx))
    denom = jnp.sum(ex, axis=-1, keepdims=True)

    # paper Alg. 6 line 15: pruned positions contribute exp(0 - max) each.
    ok_full = jnp.broadcast_to(ok, (nrb, Bb, K, Bb))
    stored = jnp.sum(ok_full[None, None, None].astype(jnp.int32), axis=(-2, -1)) \
        .reshape(1, 1, 1, nrb, Bb, 1)  # valid stored entries per row
    if cfg.causal:
        abs_pos = abs_rows[:, None] + jnp.arange(Bb)[None, :]
        row_total = (abs_pos + 1)[None, None, None, ..., None]
        if cfg.sliding_window:
            row_total = jnp.minimum(row_total, cfg.sliding_window)
    else:
        row_total = jnp.full((1, 1, 1, nrb, Bb, 1), L)
    zeros_cnt = jnp.maximum(row_total - stored, 0).astype(jnp.float32)
    denom = denom + zeros_cnt * jnp.exp(-mx)

    probs = (ex / denom).astype(q.dtype)
    probs = probs.reshape(B, KV, G, nrb, Bb, K, Bb)
    out = jnp.einsum("bkgrpcq,brcqkh->brpkgh", probs, vg)
    return out.reshape(B, Sq, H, hd)


def _decode_pattern_cols(pos, col_idx, nvalid, batch: int, block: int):
    """Per-row pattern columns for one-token decode: the query position's
    row-block selects its (K,) column blocks. Returns (posb (B,), colc (B,K)
    clipped column-block ids, valid (B,K) table-validity mask). Rows past
    the table clamp to the last row-block (serving callers size the plan to
    cover the cache)."""
    nrb, Kp = col_idx.shape
    posb = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos)), (batch,)) \
        .astype(jnp.int32)
    rb = jnp.clip(posb // block, 0, nrb - 1)
    cols = col_idx[rb]                                    # (B, K)
    nval = nvalid[rb]                                     # (B,)
    valid = (jnp.arange(Kp)[None, :] < nval[:, None]) & (cols >= 0)
    return posb, jnp.clip(cols, 0, None), valid


def _decode_gathered(cfg, q, kg, vg, posb, colc, valid, *, block: int,
                     ring_len=None):
    """Attend q over gathered pattern blocks kg/vg (B, K, block, KV, hd)
    with the Alg. 6 zero-corrected softmax. `colc`/`valid` are the logical
    column-block ids and validity from `_decode_pattern_cols` (possibly
    further masked by the caller — e.g. unmapped page-table entries);
    `ring_len` is the ring-buffer length for sliding-window caches (None
    for append caches). Shared by the contiguous and paged decode paths,
    which therefore agree bitwise when they gather the same blocks."""
    B, _, H, hd = q.shape
    KV = kg.shape[3]
    G = H // KV
    Kp = colc.shape[1]
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bcqkh->bkgcq", qg, kg).astype(jnp.float32) / np.sqrt(hd)
    # absolute positions the gathered slots are *supposed* to hold
    kpos = (colc * block)[:, :, None] + jnp.arange(block)[None, None, :]
    ok = valid[:, :, None] & (kpos >= 0) & (kpos <= posb[:, None, None])
    if cfg.sliding_window:
        ok = ok & (kpos > posb[:, None, None] - cfg.sliding_window)
    if ring_len is not None:
        # the ring holds only the last ring_len positions; older ones were
        # overwritten
        ok = ok & (kpos > posb[:, None, None] - ring_len)
    s = jnp.where(ok[:, None, None], s, -jnp.inf)
    sflat = s.reshape(B, KV, G, Kp * block)
    mx = jnp.maximum(jnp.max(sflat, axis=-1, keepdims=True), -1e30)
    ex = jnp.where(jnp.isneginf(sflat), 0.0, jnp.exp(sflat - mx))
    denom = jnp.sum(ex, axis=-1, keepdims=True)
    # Alg. 6 zero-correction: pruned visible positions count exp(-max) each
    stored = jnp.sum(ok, axis=(1, 2)).astype(jnp.int32)   # (B,)
    row_total = posb + 1
    if cfg.sliding_window:
        row_total = jnp.minimum(row_total, cfg.sliding_window)
    if ring_len is not None:
        # positions that rotated out of the ring are GONE, not pruned: the
        # dense ring decode renormalises over what the cache holds, and a
        # ring shorter than the window must match it, not the full-window
        # prefill it can no longer represent
        row_total = jnp.minimum(row_total, ring_len)
    zeros_cnt = jnp.maximum(row_total - stored, 0)[:, None, None, None] \
        .astype(jnp.float32)
    denom = denom + zeros_cnt * jnp.exp(-mx)
    probs = (ex / denom).astype(q.dtype).reshape(B, KV, G, Kp, block)
    out = jnp.einsum("bkgcq,bcqkh->bkgh", probs, vg)
    return out.reshape(B, 1, H, hd)


def sparse_decode_attention(cfg, q, k_cache, v_cache, pos, col_idx, nvalid,
                            *, block: int, ring: bool = False):
    """One-token sparse decode: attend over ONLY the KV-cache blocks the
    pattern lists for the query position's row-block (DESIGN.md §11).

    q (B,1,H,hd); caches (B,S,KV,hd); pos scalar or (B,) per-row absolute
    positions; col_idx (nrb, K) / nvalid (nrb,) — one layer's forward BCSR.
    The row-block `pos // block` selects at most K column blocks; those
    K*block cache slots are gathered and attended, so decode cost is
    O(K*block) instead of O(S_cache) — the inference payoff of the
    layer-wise pattern.

    Semantics match the sparse prefill exactly (paper Alg. 6 line 15):
    pruned causal positions contribute exp(0 - max) each to the softmax
    denominator, so a token decoded at position p produces the same
    distribution the sparse forward produces at row p (tested). Where the
    listed blocks cover every visible position the correction vanishes and
    sparse decode equals DENSE decode to kernel tolerances.

    ring=True for sliding-window ring-buffer caches (cache slot of absolute
    position p is p % S; S must be a multiple of `block`): listed column
    blocks wrap into storage blocks mod S/block, and positions that have
    rotated out of the ring are masked. Rows past the table (pos >= nrb *
    block — generation beyond the pattern's coverage) clamp to the last
    row-block; serving callers should size the plan to cover the cache
    (launch/serve.ServeEngine enforces it). Decode is causal by
    construction (a cache never holds the future), so the row total is
    pos + 1 (clipped by the sliding window) regardless of cfg.causal."""
    B, _, _H, hd = q.shape
    KV, S = k_cache.shape[2], k_cache.shape[1]
    nbc = S // block
    posb, colc, valid = _decode_pattern_cols(pos, col_idx, nvalid, B, block)
    if ring:
        sb = colc % nbc
    else:
        # append cache: blocks beyond the cache don't exist — mask, never alias
        valid = valid & (colc < nbc)
        sb = jnp.minimum(colc, nbc - 1)
    kb = k_cache.reshape(B, nbc, block, KV, hd)
    vb = v_cache.reshape(B, nbc, block, KV, hd)
    idx = sb[:, :, None, None, None]
    kg = jnp.take_along_axis(kb, idx, axis=1).astype(q.dtype)  # (B,K,blk,KV,hd)
    vg = jnp.take_along_axis(vb, idx, axis=1).astype(q.dtype)
    return _decode_gathered(cfg, q, kg, vg, posb, colc, valid, block=block,
                            ring_len=S if ring else None)


def paged_sparse_decode_attention(cfg, q, kp, vp, layer, pos, page_table,
                                  col_idx, nvalid, *, page: int,
                                  ring: bool = False):
    """`sparse_decode_attention` over a paged KV pool (core.kv_pool): the
    pattern's column blocks resolve through the request's page-table row
    instead of reshaping a contiguous per-slot cache — the O(K*block)
    gather becomes pure page indirection.

    q (B,1,H,hd); kp/vp (L, num_pages, page, KV, hd) with page == the BCSR
    block; `layer` the (traced) pool layer index; page_table (B, NB) of
    physical page ids, -1 = unmapped (masked — reads clamp to the scratch
    page, whose finite junk contributes exactly 0 through the softmax).
    ring=True recycles table slots mod NB exactly like the contiguous ring
    recycles storage blocks, so rotated-out positions reuse the same
    physical pages in place. Where every pattern-listed block is mapped the
    result is bitwise-identical to the contiguous path (same gathered
    values through the same `_decode_gathered` math — tested)."""
    B = q.shape[0]
    NB = page_table.shape[1]
    posb, colc, valid = _decode_pattern_cols(pos, col_idx, nvalid, B, page)
    if ring:
        sb = colc % NB
    else:
        valid = valid & (colc < NB)
        sb = jnp.minimum(colc, NB - 1)
    praw = jnp.take_along_axis(page_table, sb, axis=1)     # (B, K)
    valid = valid & (praw >= 0)
    phys = jnp.maximum(praw, 0)
    kg = kp[layer, phys].astype(q.dtype)                   # (B,K,page,KV,hd)
    vg = vp[layer, phys].astype(q.dtype)
    return _decode_gathered(cfg, q, kg, vg, posb, colc, valid, block=page,
                            ring_len=NB * page if ring else None)


def bcsr_attention_ops(cfg, bcsr: BCSR):
    """Analytic op count of the sparse path (paper §4.4 formula, per head):
    2*C*(2*hd+1) - L*(hd+1) with C = stored element count."""
    C = int(jnp.sum(bcsr.nvalid)) * bcsr.block * bcsr.block
    L = bcsr.seq_len
    hd = cfg.resolved_head_dim
    return 2 * C * (2 * hd + 1) - L * (hd + 1)
