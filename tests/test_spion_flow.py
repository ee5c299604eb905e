"""SPION 3-phase controller + end-to-end training integration."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SpionConfig, get_config
from repro.core.spion import SpionController, SpionState
from repro.core.variants import fixed_pattern_tables, lsh_attention
from repro.launch.train import Trainer
from repro.models.registry import build


def _controller(**kw):
    base = dict(enabled=True, variant="cf", conv_filter_size=7, block_size=16,
                alpha_quantile=0.9, transition_tol=0.05, min_dense_epochs=1,
                max_dense_epochs=10)
    base.update(kw)
    return SpionController(SpionConfig(**base), causal=False, seq_len=64)


def _pooled(rng, Ly=2, n=4):
    return rng.random((Ly, n, n))


def test_transition_on_stable_frobenius(rng):
    ctl = _controller()
    st = SpionState()
    pooled = _pooled(rng)
    frob = np.array([5.0, 5.0])
    # identical frobenius every epoch -> distances 0,0 -> |d1-d0| < tol
    for _ in range(3):
        st = ctl.observe_epoch(st, pooled, frob)
    assert st.phase == "sparse"
    assert st.tables is not None
    assert st.tables["col_idx"].shape[0] == 2  # per-layer patterns
    # generation builds the full SparsityPlan: transposed tables at KT* + stats
    Ly, nrb, _ = st.tables["col_idx"].shape
    kt = st.plan_stats["kt_star"]
    assert st.tables["row_idx"].shape == (Ly, nrb, kt)
    assert st.tables["nvalid_t"].shape == (Ly, nrb)
    assert 1 <= kt <= nrb
    assert st.plan_stats["dkv_grid_shrink"] >= 1.0


def test_no_transition_while_unstable(rng):
    ctl = _controller(transition_tol=1e-6, max_dense_epochs=100)
    st = SpionState()
    pooled = _pooled(rng)
    for e in range(5):
        frob = np.array([float(2 ** e), float(2 ** e)])  # diverging distances
        st = ctl.observe_epoch(st, pooled, frob)
    assert st.phase == "dense"


def test_forced_transition_at_max_epochs(rng):
    ctl = _controller(transition_tol=0.0, max_dense_epochs=3)
    st = SpionState()
    pooled = _pooled(rng)
    for e in range(3):
        st = ctl.observe_epoch(st, pooled, np.array([float(e * 100), 0.0]))
    assert st.phase == "sparse"


def test_state_serialization_roundtrip(rng):
    ctl = _controller()
    st = SpionState()
    pooled = _pooled(rng)
    for _ in range(3):
        st = ctl.observe_epoch(st, pooled, np.array([1.0, 1.0]))
    d = st.to_py()
    st2 = SpionState.from_py(d)
    assert st2.phase == st.phase
    for k in ("col_idx", "nvalid", "row_idx", "nvalid_t"):
        np.testing.assert_array_equal(np.asarray(st2.tables[k]),
                                      np.asarray(st.tables[k]))
    assert st2.plan_stats == st.plan_stats


def test_state_serialization_binary_arrays_path(rng):
    """to_py(include_tables=False) + table_arrays() round-trips the plan via
    the checkpoint extra_arrays channel (no JSON-encoded tables)."""
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    d = st.to_py(include_tables=False)
    assert "tables" not in d and d["tables_meta"]["block"] == 16
    st2 = SpionState.from_py(d, st.table_arrays())
    for k in ("col_idx", "nvalid", "row_idx", "nvalid_t"):
        np.testing.assert_array_equal(np.asarray(st2.tables[k]),
                                      np.asarray(st.tables[k]))
    assert st2.tables["block"] == st.tables["block"]


def test_state_meta_without_arrays_fails_loudly(rng):
    """tables_meta promises binary plan arrays; restoring without them must
    raise, not silently resume the sparse phase with tables=None."""
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    d = st.to_py(include_tables=False)
    with pytest.raises(ValueError, match="plan arrays"):
        SpionState.from_py(d)


def test_legacy_state_without_plan_rebuilds_transposed_tables(rng):
    """A pre-SparsityPlan checkpoint (forward tables only) must not silently
    drop the transposed tables on resume — from_py rebuilds them host-side."""
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    d = st.to_py()
    legacy_tables = {k: d["tables"][k] for k in ("col_idx", "nvalid", "block")}
    st2 = SpionState.from_py({**d, "tables": legacy_tables, "plan_stats": None})
    np.testing.assert_array_equal(np.asarray(st2.tables["row_idx"]),
                                  np.asarray(st.tables["row_idx"]))
    np.testing.assert_array_equal(np.asarray(st2.tables["nvalid_t"]),
                                  np.asarray(st.tables["nvalid_t"]))
    assert st2.plan_stats["kt_star"] == st.plan_stats["kt_star"]


def test_trainer_three_phase_and_loss_decreases(tmp_path):
    cfg = get_config("spion-lra").replace(
        num_layers=2, d_ff=128, vocab_size=64,
        spion=SpionConfig(enabled=True, variant="cf", conv_filter_size=5,
                          block_size=16, alpha_quantile=0.85,
                          transition_tol=1e9, min_dense_epochs=1,
                          max_dense_epochs=3))
    tr = Trainer(cfg, seq_len=64, batch=8, lr=1e-3, steps_per_epoch=5,
                 ckpt_dir=str(tmp_path))
    losses = tr.train(40, ckpt_every=20, log_every=100, log=lambda *a: None)
    assert tr.spion_state.phase == "sparse", "transition must have happened"
    assert 0 < tr.spion_state.density < 1
    assert np.mean(losses[-8:]) < np.mean(losses[:8]), "loss should decrease"


def test_trainer_checkpoint_resume(tmp_path):
    cfg = get_config("spion-lra").replace(num_layers=2, d_ff=64, vocab_size=64,
                                          spion=SpionConfig(enabled=False))
    tr = Trainer(cfg, seq_len=32, batch=4, ckpt_dir=str(tmp_path), seed=3)
    tr.train(10, ckpt_every=10, log_every=100, log=lambda *a: None)
    w_before = np.asarray(jax.device_get(
        jax.tree_util.tree_leaves(tr.params)[0]))
    tr2 = Trainer(cfg, seq_len=32, batch=4, ckpt_dir=str(tmp_path), seed=99)
    assert tr2.maybe_resume()
    assert tr2.step == 10
    w_after = np.asarray(jax.device_get(
        jax.tree_util.tree_leaves(tr2.params)[0]))
    np.testing.assert_allclose(w_before, w_after)


def test_sparse_phase_matches_dense_when_full_pattern():
    """With alpha=0 the generated pattern keeps every block -> sparse forward
    must equal dense forward (up to the zero-correction, which vanishes)."""
    cfg = get_config("spion-lra").replace(num_layers=2, d_ff=64, vocab_size=64)
    b = build(cfg)
    params = b.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 64), 0, 64)}
    dense, _ = b.forward(params, batch)
    tabs = fixed_pattern_tables("window", 64, 16, cfg.num_layers, window=9999)
    sparse, _ = b.forward(params, batch, spion=tabs)
    np.testing.assert_allclose(np.asarray(dense, np.float32),
                               np.asarray(sparse, np.float32), atol=2e-2)


def test_trainer_sparse_phase_resume_preserves_plan(tmp_path):
    """Resume in the sparse phase restores the FULL SparsityPlan (incl. the
    transposed tables, persisted binary via checkpoint extra_arrays)."""
    cfg = get_config("spion-lra").replace(
        num_layers=2, d_ff=64, vocab_size=64,
        spion=SpionConfig(enabled=True, variant="cf", conv_filter_size=5,
                          block_size=16, alpha_quantile=0.85,
                          transition_tol=1e9, min_dense_epochs=1,
                          max_dense_epochs=2))
    tr = Trainer(cfg, seq_len=64, batch=4, steps_per_epoch=5,
                 ckpt_dir=str(tmp_path))
    tr.train(20, ckpt_every=20, log_every=100, log=lambda *a: None)
    assert tr.spion_state.phase == "sparse"
    tr2 = Trainer(cfg, seq_len=64, batch=4, steps_per_epoch=5,
                  ckpt_dir=str(tmp_path), seed=7)
    assert tr2.maybe_resume()
    assert tr2.spion_state.phase == "sparse"
    for k in ("col_idx", "nvalid", "row_idx", "nvalid_t"):
        np.testing.assert_array_equal(np.asarray(tr2.spion_state.tables[k]),
                                      np.asarray(tr.spion_state.tables[k]))
    assert tr2.spion_state.plan_stats == tr.spion_state.plan_stats


def test_dryrun_tables_emit_plan_shapes():
    from repro.launch.steps import spion_dryrun_tables, spion_table_pspecs
    cfg = get_config("spion-lra").replace(num_layers=3)
    t = spion_dryrun_tables(cfg, 256)
    Ly, nrb, _ = t["col_idx"].shape
    assert Ly == 3 and nrb == 256 // t["block"]
    assert t["row_idx"].shape == (Ly, nrb, t["kt_star"])
    assert t["nvalid_t"].shape == (Ly, nrb)
    assert int(t["nvalid_t"].max()) == t["kt_star"] <= nrb
    specs = spion_table_pspecs(t)
    assert set(specs) == set(t)
    assert specs["block"] is None and specs["kt_star"] is None
    assert all(specs[k] is not None for k in
               ("col_idx", "nvalid", "row_idx", "nvalid_t"))


def test_plan_removes_transpose_from_train_step_hlo():
    """Acceptance: with a SparsityPlan supplied, the jitted fused-kernel
    train step contains NO under-jit bcsr_transpose (its argsort lowers to
    HLO sort); the plan-less fallback does."""
    import jax.numpy as jnp

    from repro.launch.steps import make_train_step, spion_dryrun_tables
    from repro.optim import adamw_init

    cfg = get_config("spion-lra").replace(
        num_layers=1, d_ff=32, d_model=32, num_heads=2, num_kv_heads=2,
        vocab_size=64,
        spion=SpionConfig(enabled=True, block_size=16))
    L = 64
    tables = spion_dryrun_tables(cfg, L)
    bundle = build(cfg)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.ndim >= 2 else x,
        bundle.init(jax.random.key(0)))
    opt = adamw_init(params)
    batch = {"tokens": jnp.zeros((2, L), jnp.int32),
             "labels": jnp.zeros((2, L), jnp.int32)}
    step = jax.jit(make_train_step(cfg, spion=True, sparse_kernel="fused"))
    hlo_plan = step.lower(params, opt, batch, jnp.int32(0), tables).as_text()
    assert "sort(" not in hlo_plan
    baseline = {k: tables[k] for k in ("col_idx", "nvalid", "block")}
    hlo_base = step.lower(params, opt, batch, jnp.int32(0), baseline).as_text()
    assert "sort(" in hlo_base


def test_lsh_attention_baseline_shape_and_locality():
    q = jax.random.normal(jax.random.key(0), (2, 128, 4, 16))
    out = lsh_attention(q, q, q, num_hashes=2, bucket_size=32)
    assert out.shape == q.shape
    assert not bool(jnp.isnan(out).any())


def test_spion_kwargs_gated_on_cfg_enabled(rng):
    """A sparse-phase state restored under a SPION-disabled config must NOT
    inject the tables into the step (regression: spion_kwargs ignored
    cfg.enabled, so restore-with-disabled-config silently trained sparse)."""
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    assert st.phase == "sparse" and st.tables is not None
    assert ctl.spion_kwargs(st) is not None
    disabled = SpionController(
        SpionConfig(enabled=False, variant="cf", conv_filter_size=7,
                    block_size=16), causal=False, seq_len=64)
    assert disabled.spion_kwargs(st) is None
    # the capture path was already gated; keep them consistent
    assert disabled.capture_kwargs(SpionState()) is None


def test_from_py_arrays_without_tables_fails_loudly(rng):
    """Plan arrays supplied against a state dict with neither 'tables' nor
    'tables_meta' is a mismatched checkpoint pair; silently dropping the
    arrays used to resume the sparse phase with tables=None (dense steps
    forever). Must raise instead."""
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    arrays = st.table_arrays()
    d = st.to_py(include_tables=False)
    del d["tables_meta"]
    with pytest.raises(ValueError, match="neither 'tables' nor 'tables_meta'"):
        SpionState.from_py(d, arrays)
    # arrays=None with a plain dense-state dict still restores fine
    dense = SpionState().to_py()
    assert SpionState.from_py(dense).tables is None


def test_plan_stats_carry_halo_extents(rng):
    """Pattern generation records the seq-parallel halo bounds (DESIGN.md
    §10) so the trainer can rebuild the sparse step with the static halo."""
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    stats = st.plan_stats
    Ly = st.tables["col_idx"].shape[0]
    assert len(stats["col_extent_left"]) == Ly
    assert len(stats["col_extent_right"]) == Ly
    assert stats["halo"] == [max(stats["col_extent_left"]),
                             max(stats["col_extent_right"])]
    # round-trips through the JSON checkpoint channel unchanged
    st2 = SpionState.from_py(st.to_py())
    assert st2.plan_stats["halo"] == stats["halo"]


def test_plan_stats_dkv_skipped_share(rng):
    """The plan records the share of its padded transposed table that the
    dK/dV kernel skips (it streams only each column's nvalid_t entries),
    and the number survives the JSON checkpoint channel."""
    import json
    ctl = _controller()
    st = SpionState()
    for _ in range(3):
        st = ctl.observe_epoch(st, _pooled(rng), np.array([1.0, 1.0]))
    nvt = np.asarray(st.tables["nvalid_t"])
    Ly, ncb = nvt.shape
    kt = st.plan_stats["kt_star"]
    share = st.plan_stats["dkv_skipped_share"]
    assert 0.0 <= share < 1.0
    assert share == 1.0 - float(nvt.sum()) / float(Ly * ncb * kt)
    st2 = SpionState.from_py(json.loads(json.dumps(st.to_py())))
    assert st2.plan_stats["dkv_skipped_share"] == share
