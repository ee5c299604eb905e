"""The `dense_attention` family: a stack of pre-norm attention and MLP
layers (GQA, optional QKV bias and rotary or learned positions, LayerNorm
or RMSNorm, ReLU or SiLU-gated MLP) under an embedding and an output
head. Everything the benchmark needs of such a model besides the program:

- `dims`: the model as its configuration file states it, in the names the
  reference and the work counts use; `PROGRAM_FIELDS` and `program_dims`
  hold the program's configuration to it;
- the plain reference: the transformer in straightforward `jax.numpy`,
  float32 at full matmul precision, with no kernel, cache or batching
  (`train_steps`, `decoder_logits`, `visible`). It imports nothing of the
  program;
- the map from the program's weight names to the reference's
  (`weights_fn`, `_layer_tree`);
- the model-level work counts (`matmul_params`, `weight_bytes`,
  `decode_cache_bytes`, `train_flops_per_step`, `prefill_flops`); the
  kernels' own counts are in bench/flops.py.

Attention follows SPION's sparse softmax (arXiv:2309.12578, Alg. 6): row
i attends the positions its plan row lists (and, for a decoder, only
positions <= i); every visible position the plan leaves out counts as a
score of 0 in the denominator and adds no value. With every block listed
this is the ordinary softmax.

`PRECISIONS` picks how each matmul rounds its operands: `f32` rounds
nothing; `fp8` is a control, a float8 (e4m3) matmul with a scale per
tensor on its operands, forward and backward, the step below the bfloat16
the configurations state; `int8` is the same with int8 operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
I8_MAX = 127.0


# published (Hugging Face style) keys -> the program's ModelConfig fields
PROGRAM_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "qkv_bias": "qkv_bias",
}


def dims(conf: dict) -> dict:
    """The model as the configuration file states it, in the names the
    reference and the work counts use."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "d_model": d, "num_heads": h,
        "num_kv_heads": conf.get("num_key_value_heads", h),
        "head_dim": conf.get("head_dim", d // h),
        "d_ff": conf["intermediate_size"],
        "num_layers": conf["num_hidden_layers"],
        "vocab_size": conf["vocab_size"],
        "gated_mlp": conf["hidden_act"] == "silu",
        "qkv_bias": bool(conf.get("qkv_bias", False)),
        "norm": "rmsnorm" if "rms_norm_eps" in conf else "layernorm",
        "norm_eps": conf.get("rms_norm_eps", conf.get("layer_norm_eps")),
        "rope_theta": conf.get("rope_theta", 0.0),
        "causal": bool(conf["causal"]),
        "dtype": conf["torch_dtype"],
    }


def program_dims(cfg) -> dict:
    """The program's ModelConfig read in `dims`' names, for the keys both
    state."""
    return {"d_model": cfg.d_model, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "num_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
            "gated_mlp": cfg.act == "silu", "qkv_bias": cfg.qkv_bias,
            "causal": cfg.causal, "dtype": cfg.dtype}


def q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def einsum_f32(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def q_int8(x):
    """Round to int8 with one scale for the whole tensor."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / I8_MAX
    return jnp.clip(jnp.round(x / s), -I8_MAX, I8_MAX) * s


def quantized_einsum(q):
    """An einsum whose operands, and the gradient it passes back, are
    rounded by `q` before a float32 product."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def es(spec, a, b):
        return einsum_f32(spec, q(a), q(b))

    def fwd(spec, a, b):
        qa, qb = q(a), q(b)
        return einsum_f32(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: einsum_f32(spec, x, y), qa, qb)
        return vjp(q(g))

    es.defvjp(fwd, bwd)
    return es


PRECISIONS = {"f32": einsum_f32, "fp8": quantized_einsum(q8),
              "int8": quantized_einsum(q_int8)}


# -- pieces -------------------------------------------------------------------

def layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def norm(m, p, x):
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    if m["norm"] == "layernorm":
        return layernorm(p, x, m["norm_eps"])
    return rmsnorm(p, x, m["norm_eps"])


def rope(x, pos, theta):
    """Rotary embedding, halves convention: x (..., S, heads, hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(block_mask, seq_len, causal):
    """(S, S) bool: the positions each row attends, from an (nrb, ncb)
    block mask; and (S,) the row's count of visible positions."""
    bm = np.asarray(block_mask, bool)
    blk = seq_len // bm.shape[0]
    m = np.repeat(np.repeat(bm, blk, 0), blk, 1)[:seq_len, :seq_len]
    if causal:
        m &= np.tri(seq_len, dtype=bool)
        total = np.arange(1, seq_len + 1)
    else:
        total = np.full(seq_len, seq_len)
    return m, total


def attention(es, q, k, v, mask, total):
    """q (B,S,H,hd), k/v (B,S,KV,hd), mask (S,S), total (S,)."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = es("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, -1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    pruned = (total - jnp.sum(mask, -1))[:, None].astype(jnp.float32)
    p = e / (jnp.sum(e, -1, keepdims=True) + pruned * jnp.exp(-m))
    return es("bhqk,bkhd->bqhd", p, v)


def block(m, es, p, h, mask, total, pos):
    """One layer of the model `m` on h (B, S, d)."""
    B, S, _ = h.shape
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    a = p["attn"]
    x = norm(m, p["attn_norm"], h)
    q = es("bsd,de->bse", x, a["wq"])
    k = es("bsd,de->bse", x, a["wk"])
    v = es("bsd,de->bse", x, a["wv"])
    if "bq" in a:
        q, k, v = (q + a["bq"].astype(jnp.float32),
                   k + a["bk"].astype(jnp.float32),
                   v + a["bv"].astype(jnp.float32))
    q, k, v = (q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd),
               v.reshape(B, S, KV, hd))
    if m["rope_theta"]:
        q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    ctx = attention(es, q, k, v, mask, total).reshape(B, S, H * hd)
    h = h + es("bse,ed->bsd", ctx, a["wo"])
    x = norm(m, p["mlp_norm"], h)
    f = p["mlp"]
    u = es("bsd,df->bsf", x, f["w_in"])
    if "w_gate" in f:
        u = jax.nn.silu(es("bsd,df->bsf", x, f["w_gate"])) * u
    else:
        u = jax.nn.relu(u)
    return h + es("bsf,fd->bsd", u, f["w_out"])


def embed(params, tokens):
    h = jnp.take(params["tok_embed"]["w"], tokens, axis=0).astype(jnp.float32)
    if "pos_embed" in params:
        h = h + params["pos_embed"]["w"][:tokens.shape[1]].astype(jnp.float32)
    return h


def head(m, es, params, h):
    w = params["lm_head" if "lm_head" in params else "tok_embed"]["w"]
    return es("bsd,vd->bsv", norm(m, params["final_norm"], h), w)


def layer_params(params, i):
    return jax.tree_util.tree_map(lambda x: x[i], params["layers"])


# -- training -----------------------------------------------------------------

def lm_loss_sum(m, es, params, tokens, labels, masks, totals):
    """Summed next-token cross-entropy of a batch (all positions)."""
    S = tokens.shape[1]
    pos = jnp.arange(S)
    h = embed(params, tokens)
    for i in range(m["num_layers"]):
        h = jax.checkpoint(functools.partial(block, m, es))(
            layer_params(params, i), h, masks[i], totals, pos)
    logits = head(m, es, params, h)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(logz - gold)


def loss_and_grad(m, precision, params, batch, masks, totals, chunk):
    """Mean loss and its gradient over the batch, `chunk` rows at a time."""
    es = PRECISIONS[precision]
    toks, labs = batch["tokens"], batch["labels"]
    B, S = toks.shape
    f = jax.value_and_grad(
        lambda p, t, l: lm_loss_sum(m, es, p, t, l, masks, totals))

    def body(acc, xs):
        l, g = f(params, *xs)
        return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(
        body, (jnp.float32(0), zeros),
        (toks.reshape(B // chunk, chunk, S), labs.reshape(B // chunk, chunk, S)))
    n = B * S
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def lr_at(opt, step):
    """Linear warm-up, then cosine decay to `floor` of the peak."""
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * min(1.0, (step + 1) / warm)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (opt["floor"] + (1 - opt["floor"]) * 0.5 *
                   (1 + np.cos(np.pi * frac)))


def adamw(opt, params, grads, state, step):
    """Global-norm clipping, then AdamW (decay on matrices only). Returns
    (params, state, the clipped gradient, its norm before the clip)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip"] / (gn + 1e-6))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    t = state["count"] + 1
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], lr_at(opt, step)

    def upd(p, g, mu, nu):
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        u = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t)) + eps)
        wd = opt["weight_decay"] if p.ndim >= 2 else 0.0
        return p - lr * (u + wd * p), mu, nu

    out = jax.tree_util.tree_map(upd, params, grads, state["mu"], state["nu"])
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"mu": pick(1), "nu": pick(2), "count": t}, grads, gn


def train_steps(m, opt, precision, params, batches, masks, totals, *,
                first_step, chunk):
    """The reference's first len(batches) steps from `params` with a fresh
    optimizer. Returns (losses, each step's gradient norm before the clip,
    the first step's clipped gradient, the parameters after the last
    step)."""
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                    params)
    state = {"mu": jax.tree_util.tree_map(jnp.zeros_like, params),
             "nu": jax.tree_util.tree_map(jnp.zeros_like, params), "count": 0}
    masks = [jnp.asarray(x) for x in masks]
    totals = jnp.asarray(totals)
    lg = jax.jit(functools.partial(loss_and_grad, m, precision, chunk=chunk))
    losses, gnorms, grad1 = [], [], None
    for i, b in enumerate(batches):
        loss, g = lg(params, {k: jnp.asarray(v) for k, v in b.items()},
                     masks, totals)
        params, state, g, gn = adamw(opt, params, g, state, first_step + i)
        losses.append(float(loss))
        gnorms.append(float(gn))
        if grad1 is None:
            grad1 = g
    return losses, gnorms, grad1, params


# -- serving ------------------------------------------------------------------

def decoder_logits(m, precisions, weights_fn, tokens, rows, masks, totals):
    """Logits of a decoder at the positions `rows` of each sequence.

    tokens (B, S) int32; rows (B, R) positions to read (padded with 0);
    masks [(S, S)] per layer. `weights_fn(name, layer)` gives one leaf: the
    weights are drawn one layer at a time, so a reference of a model that
    the chip cannot hold in float32 still fits. Returns {precision:
    (B, R, V) logits} for each precision, all from the same weights."""
    S = tokens.shape[1]
    pos = jnp.arange(S)
    emb = weights_fn("tok_embed/w", None)
    h0 = jnp.take(emb, tokens, axis=0).astype(jnp.float32)
    del emb
    hs = {p: h0 for p in precisions}
    layer = jax.jit(functools.partial(_one_layer, m), static_argnums=(0,))
    for i in range(m["num_layers"]):
        lp = _layer_tree(m, weights_fn, i)
        mask = jnp.asarray(masks[i])
        for p in precisions:
            hs[p] = layer(p, lp, hs[p], mask, jnp.asarray(totals), pos)
        del lp
    fin = {"final_norm": {"scale": weights_fn("final_norm/scale", None)}}
    fin["lm_head"] = {"w": weights_fn("lm_head/w", None)}
    out = {}
    for p in precisions:
        r = jnp.asarray(rows)
        h = hs[p][jnp.arange(r.shape[0])[:, None], r]
        out[p] = jax.jit(functools.partial(head, m, PRECISIONS[p]))(fin, h)
    return out


def _one_layer(m, precision, lp, h, mask, total, pos):
    es = PRECISIONS[precision]
    return jax.lax.map(lambda x: block(m, es, lp, x[None], mask, total,
                                       pos)[0], h)


def _layer_tree(m, weights_fn, i):
    names = ["attn_norm/scale", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
             "mlp_norm/scale", "mlp/w_in", "mlp/w_out"]
    if m["gated_mlp"]:
        names.append("mlp/w_gate")
    if m["qkv_bias"]:
        names += ["attn/bq", "attn/bk", "attn/bv"]
    if m["norm"] == "layernorm":
        names += ["attn_norm/bias", "mlp_norm/bias"]
    tree = {}
    for n in names:
        a, b = n.split("/")
        tree.setdefault(a, {})[b] = weights_fn("layers/" + n, i).astype(
            jnp.float32)
    return tree


def weights_fn(shapes, seed):
    """name, layer -> float32 leaf of the program's tree `shapes`, drawn
    again from the seed. The key is made once, outside the jitted draws, so
    one program per leaf serves every layer and every seed."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    table = {weights.leaf_name(p): sd for p, sd in flat}
    key = weights.seed_key(seed)
    draw_layer = jax.jit(weights.layer_leaf, static_argnums=(1, 2, 3))
    draw_leaf = jax.jit(weights.unstacked_leaf, static_argnums=(1, 2, 3))

    def fn(name, layer):
        sd = table[name]
        if layer is None:
            x = draw_leaf(key, name, tuple(sd.shape), sd.dtype)
        else:
            x = draw_layer(key, name, tuple(sd.shape[1:]), sd.dtype, layer)
        return x.astype(jnp.float32)
    return fn


# -- work counts --------------------------------------------------------------

def matmul_params(cfg) -> int:
    """Parameters that enter a matmul once per token: attention and MLP
    projections of every layer, and the output head."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv = cfg["num_heads"], cfg["num_kv_heads"]
    attn = d * h * hd * 2 + 2 * d * kv * hd
    mlp = (3 if cfg["gated_mlp"] else 2) * d * cfg["d_ff"]
    return cfg["num_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def train_flops_per_step(cfg, plan_nvalid, *, batch, seq_len, block):
    """Model FLOPs of one training step: 6 per matmul parameter and token,
    and 3x the forward's 4 hd per listed score. Recomputation not counted."""
    mm = 6 * matmul_params(cfg) * batch * seq_len
    att = 3 * 4 * cfg["head_dim"] * cfg["num_heads"] * batch * \
        flops.attention_scores(plan_nvalid, block)
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
        flops.attention_scores(plan_nvalid, block, rows=rows, row_tokens=real)
    return 2 * body * prompt_len + 2 * d * vocab + att


def weight_bytes(cfg) -> int:
    """Bytes of weights one decode tick reads: every matmul parameter and
    the norms, at the served dtype (embedding rows are negligible)."""
    d = cfg["d_model"]
    norms = cfg["num_layers"] * 2 * d + d
    bias = cfg["num_layers"] * (cfg["num_heads"] + 2 * cfg["num_kv_heads"]) \
        * cfg["head_dim"] if cfg["qkv_bias"] else 0
    return (matmul_params(cfg) + bias) * flops.itemsize(cfg["dtype"]) + \
        norms * flops.F32


def decode_cache_bytes(cfg, plan_nvalid, positions, block):
    """Cache bytes one decode tick has to read: for every active slot at
    position p, in every layer, K and V of the blocks its row block lists."""
    per_block = 2 * block * cfg["num_kv_heads"] * cfg["head_dim"] * \
        flops.itemsize(cfg["dtype"])
    total = 0
    for nv in plan_nvalid:
        nv = np.asarray(nv)
        for p in positions:
            total += int(nv[min(int(p) // block, len(nv) - 1)])
    return total * per_block
