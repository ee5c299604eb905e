"""Compiled-lane autotuner tests (DESIGN.md §15).

Holds the two contracts the cache lives by:

  1. A KernelConfig may only ever change SPEED — tuned and default outputs
     (forward AND gradients) are bitwise identical at every pipeline depth.
  2. The on-disk cache degrades loudly, never fatally: corrupted, stale, or
     unknown-field entries warn and fall back to the default config.

Plus the integration seam: SparseAttentionExec consults the cache at
construction (concrete tables only — tracer tables skip the lookup), and
the tuned config rides its static pytree aux into the jitted step.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.attention_exec import SparseAttentionExec
from repro.core.sparse_attention import bcsr_from_blockmask
from repro.kernels import autotune
from repro.kernels.block_sparse_attn import fused_block_sparse_attention
from repro.kernels.dispatch import DEFAULT_CONFIG, KernelConfig


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets a private cache dir; never touch ~/.cache."""
    monkeypatch.setenv("SPION_AUTOTUNE_DIR", str(tmp_path / "autotune"))
    monkeypatch.delenv("SPION_AUTOTUNE", raising=False)
    yield


def _tables(rng, n=8, block=32, density=0.5):
    mask = rng.random((n, n)) < density
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, block)
    return {"col_idx": b.col_idx, "nvalid": b.nvalid}, b


def _qkv(b, hd=16, N=2, G=1):
    S = b.col_idx.shape[0] * b.block
    q = jax.random.normal(jax.random.key(0), (N, G, S, hd))
    k = jax.random.normal(jax.random.key(1), (N, S, hd))
    v = jax.random.normal(jax.random.key(2), (N, S, hd))
    return q, k, v


def _run(b, config, q, k, v):
    col = jnp.maximum(b.col_idx, 0)
    return fused_block_sparse_attention(q, k, v, col, b.nvalid,
                                        block=b.block, interpret=True,
                                        config=config)


# ---------------------------------------------------------------------------
# contract 1: configs are scheduling-only — bitwise identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 3, 5])
def test_tuned_vs_default_bitwise(depth, rng):
    """Any pipeline depth gives bitwise-identical forward AND grads vs the
    default config — depth only moves DMA issue distance, never math."""
    tables, b = _tables(rng)
    q, k, v = _qkv(b)

    def loss(config, q, k, v):
        return jnp.sum(_run(b, config, q, k, v) ** 2)

    base = _run(b, DEFAULT_CONFIG, q, k, v)
    out = _run(b, KernelConfig(depth=depth), q, k, v)
    assert np.array_equal(np.asarray(out), np.asarray(base))
    gbase = jax.grad(loss, argnums=(1, 2, 3))(DEFAULT_CONFIG, q, k, v)
    gout = jax.grad(loss, argnums=(1, 2, 3))(KernelConfig(depth=depth),
                                             q, k, v)
    for ga, gb in zip(gout, gbase):
        assert np.array_equal(np.asarray(ga), np.asarray(gb))


def test_config_json_roundtrip():
    cfg = KernelConfig(depth=3,
                       dimension_semantics=("arbitrary",) * 3)
    d = cfg.to_json()
    json.dumps(d)  # must be serialisable as-is
    assert KernelConfig.from_json(d) == cfg
    assert KernelConfig.from_json(KernelConfig().to_json()) == DEFAULT_CONFIG


def test_config_from_json_rejects_bad_entries():
    with pytest.raises(ValueError, match="unknown"):
        KernelConfig.from_json({"depth": 2, "bogus": 1})
    with pytest.raises(ValueError, match="depth"):
        KernelConfig.from_json({"depth": 0})
    with pytest.raises(ValueError, match="depth"):
        KernelConfig.from_json({"depth": "two"})


# ---------------------------------------------------------------------------
# contract 2: cache IO — roundtrip, loud fallback
# ---------------------------------------------------------------------------

def test_store_lookup_roundtrip(rng):
    tables, b = _tables(rng)
    assert autotune.lookup(tables, b.block) is None  # cold miss
    cfg = KernelConfig(depth=3)
    path = autotune.store(tables, b.block, cfg, best_us=12.5, swept=3)
    assert os.path.exists(path)
    assert path.startswith(autotune.cache_dir())
    assert autotune.lookup(tables, b.block) == cfg
    # a different dtype is a different key
    assert autotune.lookup(tables, b.block, dtype=jnp.bfloat16) is None


def test_corrupted_entry_warns_and_falls_back(rng):
    tables, b = _tables(rng)
    path = autotune.store(tables, b.block, KernelConfig(depth=3))
    with open(path, "w") as f:
        f.write("not json {{{")
    with pytest.warns(UserWarning, match="unusable cache entry"):
        assert autotune.lookup(tables, b.block) is None


def test_stale_version_warns_and_falls_back(rng):
    tables, b = _tables(rng)
    path = autotune.store(tables, b.block, KernelConfig(depth=3))
    with open(path) as f:
        entry = json.load(f)
    entry["version"] = 0
    with open(path, "w") as f:
        json.dump(entry, f)
    with pytest.warns(UserWarning, match="stale"):
        assert autotune.lookup(tables, b.block) is None


def test_unknown_config_field_warns_and_falls_back(rng):
    tables, b = _tables(rng)
    path = autotune.store(tables, b.block, KernelConfig(depth=3))
    with open(path) as f:
        entry = json.load(f)
    entry["config"]["from_the_future"] = 7
    with open(path, "w") as f:
        json.dump(entry, f)
    with pytest.warns(UserWarning, match="unknown KernelConfig fields"):
        assert autotune.lookup(tables, b.block) is None


def test_env_disable_skips_cache(rng, monkeypatch):
    tables, b = _tables(rng)
    autotune.store(tables, b.block, KernelConfig(depth=3))
    monkeypatch.setenv("SPION_AUTOTUNE", "0")
    assert not autotune.enabled()
    assert autotune.lookup(tables, b.block) is None


def test_digest_distinguishes_pattern_and_block(rng):
    tables, b = _tables(rng)
    other, _ = _tables(rng, density=0.9)
    d1 = autotune.pattern_digest(tables, b.block)
    assert d1 == autotune.pattern_digest(tables, b.block)  # deterministic
    assert d1 != autotune.pattern_digest(other, b.block)
    assert d1 != autotune.pattern_digest(tables, b.block * 2)
    # transposed tables extend the digest (plan-built vs bare pattern)
    extended = dict(tables, row_idx=np.zeros((4, 4), np.int32),
                    nvalid_t=np.ones((4,), np.int32))
    assert d1 != autotune.pattern_digest(extended, b.block)


def test_candidate_sets_are_bounded():
    for backend, expect in [("interpret", 3), ("tpu", 6), ("gpu", 3)]:
        cands = autotune.candidates(backend)
        assert len(cands) == expect, backend
        assert all(isinstance(c, KernelConfig) and c.depth >= 1
                   for c in cands)


# ---------------------------------------------------------------------------
# the full lane: tune -> cache -> exec dispatch
# ---------------------------------------------------------------------------

def test_tune_end_to_end(rng):
    tables, b = _tables(rng)
    best, report = autotune.tune(tables, b.block, head_dim=16, reps=1,
                                 interpret=True)
    # every candidate was bitwise-checked against the default and passed
    assert len(report) >= len(autotune.candidates())
    assert all(r["bitwise"] for r in report)
    assert autotune.lookup(tables, b.block) == best
    q, k, v = _qkv(b)
    assert np.array_equal(np.asarray(_run(b, best, q, k, v)),
                          np.asarray(_run(b, DEFAULT_CONFIG, q, k, v)))


def test_exec_construction_consults_cache(rng):
    tables, b = _tables(rng)
    tuned = KernelConfig(depth=1)
    autotune.store(tables, b.block, tuned)
    ex = SparseAttentionExec(tables, block=b.block, kernel="fused")
    assert ex.kernel_config == tuned
    # the config is STATIC: it rides the pytree aux through jit untouched
    leaves, aux = jax.tree_util.tree_flatten(ex)
    rebuilt = jax.tree_util.tree_unflatten(aux, leaves)
    assert rebuilt.kernel_config == tuned
    # an explicit config wins over the cache
    ex2 = SparseAttentionExec(tables, block=b.block,
                              kernel_config=KernelConfig(depth=5))
    assert ex2.kernel_config == KernelConfig(depth=5)


def test_exec_attend_tuned_matches_default(rng, monkeypatch):
    cfg = get_config("spion-lra")
    tables, b = _tables(rng)
    autotune.store(tables, b.block, KernelConfig(depth=3))
    ex_tuned = SparseAttentionExec(tables, block=b.block, kernel="fused")
    assert ex_tuned.kernel_config == KernelConfig(depth=3)
    monkeypatch.setenv("SPION_AUTOTUNE", "0")
    ex_plain = SparseAttentionExec(tables, block=b.block, kernel="fused")
    assert ex_plain.kernel_config is None
    S, hd = ex_tuned.coverage, 16
    q = jax.random.normal(jax.random.key(0), (2, S, 2, hd))
    kv = jax.random.normal(jax.random.key(1), (2, S, 2, hd))
    layer = {k: jnp.asarray(v) for k, v in tables.items()}
    out_t = ex_tuned.attend(cfg, q, kv, kv, layer)
    out_p = ex_plain.attend(cfg, q, kv, kv, layer)
    assert np.array_equal(np.asarray(out_t), np.asarray(out_p))


def test_exec_construction_under_jit_is_tracer_safe(rng):
    """Tables that are tracers (the legacy dict payload crossing a jit
    boundary) must skip the cache lookup, not crash hashing a tracer."""
    tables, b = _tables(rng)
    autotune.store(tables, b.block, KernelConfig(depth=3))

    @jax.jit
    def build(col, nvalid):
        ex = SparseAttentionExec({"col_idx": col, "nvalid": nvalid},
                                 block=b.block)
        assert ex.kernel_config is None  # trace-time: lookup skipped
        return ex.tables["col_idx"].sum()

    build(jnp.asarray(tables["col_idx"]), jnp.asarray(tables["nvalid"]))


def test_describe():
    assert autotune.describe(None) == "default"
    s = autotune.describe(KernelConfig(depth=3, dimension_semantics=(
        "arbitrary",) * 3))
    assert "depth=3" in s and "dimension_semantics=" in s
