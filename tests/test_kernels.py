"""Pallas kernel validation (interpret mode): shape/dtype sweeps vs the
ref.py pure-jnp oracles and vs the BCSR jnp path, plus jax.grad checks of
the fused kernel's custom VJP against the differentiable dense reference."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.configs import get_config
from repro.core.sparse_attention import (bcsr_attention, bcsr_from_blockmask,
                                         bcsr_transpose, build_sparsity_plan,
                                         host_transpose_tables)
from repro.kernels import ref
from repro.kernels.block_sparse_attn import fused_block_sparse_attention
from repro.kernels.dispatch import (COMPILED_BACKENDS, KernelConfig,
                                    default_interpret)
from repro.kernels.ops import spion_attention_kernel


def _bcsr(rng, n, block, density=0.5):
    mask = rng.random((n, n)) < density
    np.fill_diagonal(mask, True)
    return bcsr_from_blockmask(mask, block)


SWEEP = [
    # (S, hd, block, dtype, causal, sw)
    (128, 32, 32, jnp.float32, False, None),
    (128, 32, 32, jnp.float32, True, None),
    (256, 64, 64, jnp.float32, True, 96),
    (128, 16, 32, jnp.bfloat16, True, None),
    (64, 128, 32, jnp.float32, False, None),
]

# 3-kernel-vs-fused parity after the collapse (DESIGN.md §15): the retained
# reference pipeline (ref.sddmm_ref -> ref.sparse_softmax_ref -> ref.spmm_ref,
# the demoted paper-faithful path) must match the production fused kernel on
# causal / sliding-window / GQA patterns.
PARITY_SWEEP = [
    # (causal, sw, G)
    (False, None, 1),
    (True, None, 1),
    (True, 96, 1),
    (False, 96, 2),
    (True, None, 4),     # GQA: 4 query heads share each kv head
    (False, None, 2),
]


@pytest.mark.parametrize("causal,sw,G", PARITY_SWEEP)
def test_ref_pipeline_vs_fused_parity(causal, sw, G, rng):
    """The demoted 3-kernel pipeline, staged explicitly through its three
    oracles, agrees with the single-pass fused kernel (interpreter mode so
    this holds the line on CPU CI)."""
    S, hd, block, N = 256, 32, 32, 2
    q = jax.random.normal(jax.random.key(0), (N, G, S, hd))
    k = jax.random.normal(jax.random.key(1), (N, S, hd))
    v = jax.random.normal(jax.random.key(2), (N, S, hd))
    b = _bcsr(rng, S // block, block)
    col = jnp.maximum(b.col_idx, 0)
    out = fused_block_sparse_attention(q, k, v, col, b.nvalid, block=block,
                                       causal=causal, sliding_window=sw,
                                       interpret=True)
    for g in range(G):
        s = ref.sddmm_ref(q[:, g], k, b.col_idx, block=block, causal=causal,
                          sliding_window=sw)
        p = ref.sparse_softmax_ref(s, b.col_idx, block=block, seq_len=S,
                                   causal=causal, sliding_window=sw)
        want = ref.spmm_ref(p, v, b.col_idx)
        np.testing.assert_allclose(np.asarray(out[:, g], np.float32),
                                   np.asarray(want, np.float32), atol=3e-5,
                                   err_msg=f"group {g}")


@pytest.mark.parametrize("S,hd,block,dtype,causal,sw", SWEEP)
def test_fused_kernel_vs_ref(S, hd, block, dtype, causal, sw, rng):
    N, G = 2, 2
    q = jax.random.normal(jax.random.key(0), (N, G, S, hd), dtype)
    k = jax.random.normal(jax.random.key(1), (N, S, hd), dtype)
    v = jax.random.normal(jax.random.key(2), (N, S, hd), dtype)
    b = _bcsr(rng, S // block, block)
    col = jnp.maximum(b.col_idx, 0)
    out = fused_block_sparse_attention(q, k, v, col, b.nvalid, block=block,
                                       causal=causal, sliding_window=sw,
                                       interpret=True)
    want = jnp.stack([
        ref.fused_ref(q[:, g], k, v, b.col_idx, block=block, causal=causal,
                      sliding_window=sw) for g in range(G)], axis=1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=6e-2 if dtype == jnp.bfloat16 else 3e-5)


GRAD_SWEEP = [
    # (S, hd, block, causal, sw, G)
    (128, 32, 32, False, None, 1),   # encoder
    (128, 32, 32, True, None, 1),    # causal LM
    (256, 64, 64, True, 96, 1),      # causal + sliding window
    (128, 16, 32, True, None, 4),    # GQA: 4 query heads per kv head
]


@pytest.mark.parametrize("S,hd,block,causal,sw,G", GRAD_SWEEP)
def test_fused_vjp_grads_vs_dense_ref(S, hd, block, causal, sw, G, rng):
    """jax.grad of the fused custom-VJP kernel == grad of the differentiable
    jnp reference (dense path masked to the active pattern) within 1e-3."""
    N = 2
    q = jax.random.normal(jax.random.key(0), (N, G, S, hd))
    k = jax.random.normal(jax.random.key(1), (N, S, hd))
    v = jax.random.normal(jax.random.key(2), (N, S, hd))
    b = _bcsr(rng, S // block, block)
    col = jnp.maximum(b.col_idx, 0)
    gout = jax.random.normal(jax.random.key(3), (N, G, S, hd))

    def loss_fused(q, k, v):
        o = fused_block_sparse_attention(q, k, v, col, b.nvalid, block=block,
                                         causal=causal, sliding_window=sw,
                                         interpret=True)
        return jnp.sum(o * gout)

    def loss_ref(q, k, v):
        o = jnp.stack([ref.fused_ref(q[:, g], k, v, b.col_idx, block=block,
                                     causal=causal, sliding_window=sw)
                       for g in range(G)], axis=1)
        return jnp.sum(o * gout)

    got = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-3,
                                   err_msg=f"d{name} mismatch")


def test_fused_vjp_under_jit_and_dtype(rng):
    """The custom VJP composes with jit; bf16 inputs get bf16 cotangents."""
    S, hd, block = 128, 32, 32
    q = jax.random.normal(jax.random.key(0), (2, 2, S, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (2, S, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (2, S, hd), jnp.bfloat16)
    b = _bcsr(rng, S // block, block)
    col = jnp.maximum(b.col_idx, 0)

    @jax.jit
    def g(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(
            fused_block_sparse_attention(q, k, v, col, b.nvalid, block=block,
                                         causal=True, interpret=True)
            .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    dq, dk, dv = g(q, k, v)
    assert dq.dtype == jnp.bfloat16 and dk.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(dq, np.float32)).all()
    assert float(jnp.max(jnp.abs(dv.astype(jnp.float32)))) > 0


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("n", [1, 3, 8])
def test_bcsr_transpose_roundtrip(seed, n):
    """Property: transpose . transpose == identity on the active block set."""
    r = np.random.default_rng(seed)
    mask = r.random((n, n)) < r.uniform(0.1, 0.9)
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, 8)

    def dense_of(idx, nv, ncols):
        idx, nv = np.asarray(idx), np.asarray(nv)
        out = np.zeros((idx.shape[0], ncols), bool)
        for i in range(idx.shape[0]):
            out[i, idx[i, : nv[i]]] = True
        return out

    row_idx, nvt = bcsr_transpose(b.col_idx, b.nvalid, ncb=n)
    assert np.array_equal(dense_of(row_idx, nvt, n), mask.T)
    # ascending row order within each column's active list
    ri, nv = np.asarray(row_idx), np.asarray(nvt)
    for c in range(n):
        assert np.all(np.diff(ri[c, : nv[c]]) > 0)
    back_idx, back_nv = bcsr_transpose(row_idx, nvt, ncb=n)
    assert np.array_equal(dense_of(back_idx, back_nv, n), mask)


@given(st.integers(0, 10_000), st.integers(1, 12), st.floats(0.05, 0.95))
def test_bcsr_transpose_roundtrip_property(seed, n, density):
    r = np.random.default_rng(seed)
    mask = r.random((n, n)) < density
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, 8)
    row_idx, nvt = bcsr_transpose(b.col_idx, b.nvalid, ncb=n)
    back_idx, back_nv = bcsr_transpose(row_idx, nvt, ncb=n)
    got = np.zeros((n, n), bool)
    bi, bn = np.asarray(back_idx), np.asarray(back_nv)
    for i in range(n):
        got[i, bi[i, : bn[i]]] = True
    assert np.array_equal(got, mask)


def test_bcsr_transpose_jit_and_width_clamp():
    """Runs under jit on traced tables; max_k truncates the padded width."""
    mask = np.zeros((4, 4), bool)
    mask[:, 0] = True          # global-attention stripe: col 0 in every row
    mask[2, 3] = True
    b = bcsr_from_blockmask(mask, 8)
    row_idx, nvt = jax.jit(
        lambda c, n: bcsr_transpose(c, n, ncb=4))(b.col_idx, b.nvalid)
    assert row_idx.shape == (4, 4)
    assert int(nvt[0]) == 4 and np.array_equal(np.asarray(row_idx)[0], [0, 1, 2, 3])
    ri2, nvt2 = bcsr_transpose(b.col_idx, b.nvalid, ncb=4, max_k=2)
    assert ri2.shape == (4, 2) and int(nvt2[0]) == 2


@given(st.integers(0, 10_000), st.integers(1, 12), st.floats(0.05, 0.95))
def test_host_plan_tables_match_under_jit_transpose(seed, n, density):
    """Property: the host-built SparsityPlan transposed tables agree with the
    under-jit bcsr_transpose output (valid prefixes + counts) for random
    block masks, at the plan's true width KT*."""
    r = np.random.default_rng(seed)
    mask = r.random((n, n)) < density
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, 8)
    plan = build_sparsity_plan(b.col_idx, b.nvalid, 8, ncb=n)
    kt = plan.kt_star
    assert kt == int(mask.sum(axis=0).max())          # true column population
    assert plan.tables["row_idx"].shape == (1, n, kt)
    ri_jit, nvt_jit = jax.jit(
        lambda c, v: bcsr_transpose(c, v, ncb=n, max_k=kt))(b.col_idx, b.nvalid)
    ri = np.asarray(plan.tables["row_idx"])[0]
    nvt = np.asarray(plan.tables["nvalid_t"])[0]
    np.testing.assert_array_equal(nvt, np.asarray(nvt_jit))
    ri_jit = np.asarray(ri_jit)
    for c in range(n):
        np.testing.assert_array_equal(ri[c, : nvt[c]], ri_jit[c, : nvt[c]])
    # clamped padding stays in range (the kernels index with it)
    assert ri.min() >= 0 and ri.max() < n


def test_host_transpose_single_layer_and_pinned_width():
    mask = np.zeros((4, 4), bool)
    mask[:, 0] = True
    mask[2, 3] = True
    b = bcsr_from_blockmask(mask, 8)
    ri, nvt, kt = host_transpose_tables(b.col_idx, b.nvalid, ncb=4)
    assert kt == 4 and ri.shape == (4, 4)             # stripe -> population nrb
    np.testing.assert_array_equal(ri[0], [0, 1, 2, 3])
    ri2, nvt2, kt2 = host_transpose_tables(b.col_idx, b.nvalid, ncb=4, max_kt=2)
    assert kt2 == 2 and ri2.shape == (4, 2) and int(nvt2[0]) == 2


@pytest.mark.parametrize("S,hd,block,causal,sw,G", GRAD_SWEEP)
def test_fused_vjp_plan_path_grads_vs_dense_ref(S, hd, block, causal, sw, G, rng):
    """Same contract as test_fused_vjp_grads_vs_dense_ref, but the backward
    consumes the host-built SparsityPlan transposed tables (dK/dV grid width
    KT*) instead of rebuilding them under jit at width nrb."""
    N = 2
    n = S // block
    q = jax.random.normal(jax.random.key(0), (N, G, S, hd))
    k = jax.random.normal(jax.random.key(1), (N, S, hd))
    v = jax.random.normal(jax.random.key(2), (N, S, hd))
    b = _bcsr(rng, n, block)
    col = jnp.maximum(b.col_idx, 0)
    plan = build_sparsity_plan(b.col_idx, b.nvalid, block, ncb=n)
    assert plan.tables["row_idx"].shape[-1] == plan.kt_star <= n
    gout = jax.random.normal(jax.random.key(3), (N, G, S, hd))

    def loss_plan(q, k, v):
        o = fused_block_sparse_attention(
            q, k, v, col, b.nvalid, block=block, causal=causal,
            sliding_window=sw, interpret=True,
            row_idx=plan.tables["row_idx"][0],
            nvalid_t=plan.tables["nvalid_t"][0])
        return jnp.sum(o * gout)

    def loss_ref(q, k, v):
        o = jnp.stack([ref.fused_ref(q[:, g], k, v, b.col_idx, block=block,
                                     causal=causal, sliding_window=sw)
                       for g in range(G)], axis=1)
        return jnp.sum(o * gout)

    got = jax.grad(loss_plan, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, w in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-3,
                                   err_msg=f"d{name} mismatch (plan path)")


def test_plan_path_grads_equal_fallback_path():
    """Plan-built and under-jit transposed tables must give IDENTICAL dk/dv
    (same accumulation order over ascending row-blocks, fewer grid steps)."""
    S, hd, block = 128, 16, 16
    n = S // block
    rng = np.random.default_rng(5)
    # skewed: sliding-window-ish mask where KT* < nrb
    mask = np.zeros((n, n), bool)
    for r in range(n):
        mask[r, max(r - 1, 0): r + 1] = True
    b = bcsr_from_blockmask(mask, block)
    plan = build_sparsity_plan(b.col_idx, b.nvalid, block, ncb=n)
    assert plan.kt_star < n
    col = jnp.maximum(b.col_idx, 0)
    q = jax.random.normal(jax.random.key(0), (2, 1, S, hd))
    k = jax.random.normal(jax.random.key(1), (2, S, hd))
    v = jax.random.normal(jax.random.key(2), (2, S, hd))

    def loss(q, k, v, use_plan):
        o = fused_block_sparse_attention(
            q, k, v, col, b.nvalid, block=block, causal=True, interpret=True,
            row_idx=plan.tables["row_idx"][0] if use_plan else None,
            nvalid_t=plan.tables["nvalid_t"][0] if use_plan else None)
        return jnp.sum(o ** 2)

    g_plan = jax.grad(lambda *a: loss(*a, True), argnums=(0, 1, 2))(q, k, v)
    g_base = jax.grad(lambda *a: loss(*a, False), argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(g_plan, g_base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-6)


@pytest.mark.parametrize("causal,G,config", [
    (False, 1, None), (True, 2, None), (False, 2, KernelConfig(depth=1)),
], ids=["bidir", "causal-gqa", "depth1"])
def test_dkv_padded_width_invariance(causal, G, config):
    """dK/dV streams only each column's listed row blocks, so the padded
    width of the transposed tables cannot change a bit of the gradients.
    A skewed pattern: a stripe column listed by every row block but the
    last (so KT* = nrb - 1 and pinning to nrb pads every column once more),
    a few columns listed by one row block each, and one listed by none.
    dQ, dK and dV agree bit for bit at KT* and at nrb, and the unlisted
    column's dK/dV is exactly zero."""
    S, hd, block = 128, 16, 16
    n = S // block
    mask = np.zeros((n, n), bool)
    mask[: n - 1, 0] = True                 # the stripe
    for r in range(1, n - 1):
        mask[r, r] = True                   # listed by its own row block
    mask[n - 1, n - 2] = True               # column n-1 is listed by none
    b = bcsr_from_blockmask(mask, block)
    col = jnp.maximum(b.col_idx, 0)
    ri, nvt, kt = host_transpose_tables(b.col_idx, b.nvalid, ncb=n)
    ri_n, nvt_n, kt_n = host_transpose_tables(b.col_idx, b.nvalid, ncb=n,
                                              max_kt=n)
    assert kt == n - 1 and kt_n == n and int(nvt[-1]) == 0
    assert sorted(nvt.tolist()) == [0] + [1] * (n - 3) + [2, n - 1]
    np.testing.assert_array_equal(nvt, nvt_n)
    q = jax.random.normal(jax.random.key(0), (2, G, S, hd))
    k = jax.random.normal(jax.random.key(1), (2, S, hd))
    v = jax.random.normal(jax.random.key(2), (2, S, hd))
    gout = jax.random.normal(jax.random.key(3), (2, G, S, hd))

    def grads(row_idx, nvalid_t):
        def loss(q, k, v):
            o = fused_block_sparse_attention(
                q, k, v, col, b.nvalid, block=block, causal=causal,
                interpret=True, row_idx=jnp.asarray(row_idx),
                nvalid_t=jnp.asarray(nvalid_t), config=config)
            return jnp.sum(o * gout)
        return [np.asarray(x) for x in
                jax.grad(loss, argnums=(0, 1, 2))(q, k, v)]

    tight, wide = grads(ri, nvt), grads(ri_n, nvt_n)
    for name, a, w in zip("qkv", tight, wide):
        assert np.array_equal(a, w), f"d{name} moved with the padded width"
    _, dk, dv = tight
    assert not dk[:, -block:].any() and not dv[:, -block:].any()
    assert dk[:, :block].any() and dv[:, :block].any()


def test_default_interpret_resolves_platform():
    # only backends with no accelerator resolve interpret=None to the
    # interpreter (a GPU host fails loudly at lowering instead)
    expect = jax.default_backend() not in COMPILED_BACKENDS
    assert default_interpret(None) is expect
    assert default_interpret(True) is True
    assert default_interpret(False) is False


@pytest.mark.parametrize("arch", ["spion-lra", "qwen2-7b", "mixtral-8x7b"])
@pytest.mark.parametrize("config", [None, KernelConfig(depth=1)],
                         ids=["default", "depth1"])
def test_kernel_wrapper_vs_bcsr_attention(arch, config, rng):
    """The fused kernel is the only spion_attention_kernel path; a tuned
    KernelConfig rides through the wrapper without changing results."""
    cfg = get_config(arch)
    if cfg.sliding_window:
        cfg = cfg.replace(sliding_window=96)
    B, S, H, KV, hd, blk = 2, 256, 4, 2, 32, 64
    q = jax.random.normal(jax.random.key(1), (B, S, H, hd))
    k = jax.random.normal(jax.random.key(2), (B, S, KV, hd))
    v = jax.random.normal(jax.random.key(3), (B, S, KV, hd))
    b = _bcsr(rng, S // blk, blk)
    want = bcsr_attention(cfg, q, k, v, b)
    out = spion_attention_kernel(cfg, q, k, v, b, interpret=True,
                                 config=config)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    one fixed directory inside the checkout."""
    from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert DEFAULT_DIR == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
