"""Operations and bytes of the work the benchmark's cells ask for, from
shapes and the plan alone: what any implementation of the same attention
has to do, not what one kernel happens to do.

Attention work is counted over the column blocks the plan lists for each
row block (`nvalid`), whole blocks, at the model's logical head_dim and
dtype. lse and delta count one float32 per row. Nothing here knows about
lane padding, clamped fetches past `nvalid`, or recomputation: work those
add is waste the roofline share is there to show.
"""
from __future__ import annotations

import numpy as np

F32 = 4


def itemsize(dtype: str) -> int:
    return int(np.dtype({"bfloat16": "float16"}.get(dtype, dtype)).itemsize)


def valid_blocks(nvalid, rows: int | None = None) -> int:
    """Listed blocks of one layer's plan over its first `rows` row blocks."""
    nv = np.asarray(nvalid)
    return int(nv[:rows].sum()) if rows is not None else int(nv.sum())


# -- the three fused kernels, per call (one layer) ---------------------------

def fused_fwd(*, batch, heads, hd, block, rows, blocks, dsize):
    """Forward: per (sequence, head, row block) read q, stream the listed
    K/V tiles, write o and lse. s = q k^T and o = p v: 4 hd flops a score."""
    ng = batch * heads
    flops = 4 * hd * block * block * blocks * ng
    nbytes = ng * (rows * block * (2 * hd * dsize + F32)
                   + blocks * 2 * block * hd * dsize)
    return flops, nbytes


def fused_dq(*, batch, heads, hd, block, rows, blocks, dsize):
    """dQ: per row block read q, dO, lse, delta, stream the listed K/V tiles
    (s, dp and dq += ds k: 6 hd flops a score), write dq."""
    ng = batch * heads
    flops = 6 * hd * block * block * blocks * ng
    nbytes = ng * (rows * block * (3 * hd * dsize + 2 * F32)
                   + blocks * 2 * block * hd * dsize)
    return flops, nbytes


def fused_dkv(*, batch, heads, kv_heads, hd, block, cols, blocks, dsize):
    """dK/dV: per (sequence, kv head, column block) read k and v once, stream
    q, dO, lse and delta of every (head, row block) that lists the column
    (s, dp, dv += p^T dO, dk += ds^T q: 8 hd flops a score), write dk, dv."""
    ng = batch * heads
    nkv = batch * kv_heads
    flops = 8 * hd * block * block * blocks * ng
    nbytes = (nkv * cols * block * 4 * hd * dsize
              + ng * blocks * block * (2 * hd * dsize + 2 * F32))
    return flops, nbytes


def roofline_s(flops, nbytes, peaks):
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


# -- whole model -------------------------------------------------------------

def matmul_params(cfg) -> int:
    """Parameters that enter a matmul once per token: attention and MLP
    projections of every layer, and the output head."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv = cfg["num_heads"], cfg["num_kv_heads"]
    attn = d * h * hd * 2 + 2 * d * kv * hd
    mlp = (3 if cfg["gated_mlp"] else 2) * d * cfg["d_ff"]
    return cfg["num_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def attention_scores(plan_nvalid, block, rows=None, row_tokens=None):
    """Scores over the listed blocks summed over layers: every layer's
    listed blocks times block^2, over its first `rows` row blocks. With
    `row_tokens`, each row block counts only that many real rows."""
    total = 0.0
    for nv in plan_nvalid:
        nv = np.asarray(nv)[:rows]
        w = np.full(len(nv), block, np.float64) if row_tokens is None \
            else np.asarray(row_tokens, np.float64)[:len(nv)]
        total += float(np.sum(nv * w)) * block
    return total


def train_flops_per_step(cfg, plan_nvalid, *, batch, seq_len, block):
    """Model FLOPs of one training step: 6 per matmul parameter and token,
    and 3x the forward's 4 hd per listed score. Recomputation not counted."""
    mm = 6 * matmul_params(cfg) * batch * seq_len
    att = 3 * 4 * cfg["head_dim"] * cfg["num_heads"] * batch * \
        attention_scores(plan_nvalid, block)
    return mm + att


def prefill_flops(cfg, plan_nvalid, *, prompt_len, block):
    """Model FLOPs of one prompt: 2 per matmul parameter and prompt token
    (the output head once, for the token that is served), and 4 hd per
    listed score over the row blocks that hold prompt rows."""
    d, vocab = cfg["d_model"], cfg["vocab_size"]
    body = matmul_params(cfg) - d * vocab
    rows = -(-prompt_len // block)
    real = [min(block, prompt_len - r * block) for r in range(rows)]
    att = 4 * cfg["head_dim"] * cfg["num_heads"] * \
        attention_scores(plan_nvalid, block, rows=rows, row_tokens=real)
    return 2 * body * prompt_len + 2 * d * vocab + att


def weight_bytes(cfg) -> int:
    """Bytes of weights one decode tick reads: every matmul parameter and
    the norms, at the served dtype (embedding rows are negligible)."""
    d = cfg["d_model"]
    norms = cfg["num_layers"] * 2 * d + d
    bias = cfg["num_layers"] * (cfg["num_heads"] + 2 * cfg["num_kv_heads"]) \
        * cfg["head_dim"] if cfg["qkv_bias"] else 0
    return (matmul_params(cfg) + bias) * itemsize(cfg["dtype"]) + norms * F32


def decode_cache_bytes(cfg, plan_nvalid, positions, block):
    """Cache bytes one decode tick has to read: for every active slot at
    position p, in every layer, K and V of the blocks its row block lists."""
    per_block = 2 * block * cfg["num_kv_heads"] * cfg["head_dim"] * \
        itemsize(cfg["dtype"])
    total = 0
    for nv in plan_nvalid:
        nv = np.asarray(nv)
        for p in positions:
            total += int(nv[min(int(p) // block, len(nv) - 1)])
    return total * per_block
