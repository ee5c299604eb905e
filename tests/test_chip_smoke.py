"""chip_smoke.py's phases on CPU at reduced size, with the fused kernel
forced through the Pallas interpreter by the configs handed in here; and
its refusal to run without a TPU."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fused(cfg, block):
    return cfg.replace(spion=dataclasses.replace(
        cfg.spion, kernel="fused", block_size=block))


def test_train_phase_reaches_sparse(smoke):
    cfg = _fused(get_config("spion-lra"), 16)
    f = smoke.train_phase(cfg, seq_len=128, batch=2, steps_per_epoch=2,
                          sparse_steps=3, check_batch=1)
    assert f["dense_steps"] == 4 and f["sparse_steps"] == 3
    assert np.isfinite([f["loss_first"], f["loss_last"]]).all()
    # the interpreter reproduces the float32 reference far inside the
    # chip's tolerance; no Mosaic kernel exists off TPU
    assert max(f["fused_vs_jnp_rel_err"].values()) < 1e-4
    assert not f["tpu_custom_call"]


def test_serve_phase_answers_every_request(smoke):
    cfg = _fused(get_config("qwen2-7b").reduced(), 16)
    f = smoke.serve_phase(cfg, slots=2, max_len=128, prompt_lens=[20, 45, 70],
                          prefix_len=32, suffix_lens=[3, 9, 5], max_new=4,
                          prefill_bucket=32)
    assert [e["engine"] for e in f["engines"]] == ["dense", "sparse",
                                                   "shared_prefix"]
    assert all(e["new_tokens"] == 4 * e["requests"] for e in f["engines"])
    assert f["worst_block_rel_l2"] < smoke.LOGITS_TOL
    assert not f["tpu_custom_call"]


def _drop_one_block(nvalid, layer):
    """The plan with the last column block of one row left out."""
    nv = np.array(nvalid)
    nv[layer, int(np.argmax(nv[layer]))] -= 1
    return nv


def test_attention_check_catches_a_dropped_block(smoke, monkeypatch):
    """A fused kernel that skips one column block of one row fails ATTN_TOL
    by far; the clean kernel passes it."""
    from repro.core.attention_exec import SparseAttentionExec
    from repro.launch.steps import spion_dryrun_tables
    cfg = _fused(get_config("spion-lra"), 16)
    tabs = spion_dryrun_tables(cfg, 128)
    ex = SparseAttentionExec({k: v for k, v in tabs.items()
                              if hasattr(v, "shape")}, block=16)
    clean = smoke.attention_agreement(cfg, ex, batch=1, seed=0)

    def faulty(tables, *, kernel, **kw):
        if kernel == "fused":
            tables = dict(tables, nvalid=_drop_one_block(tables["nvalid"], 0))
        return SparseAttentionExec(tables, kernel=kernel, **kw)
    monkeypatch.setattr(smoke, "SparseAttentionExec", faulty)
    bad = smoke.attention_agreement(cfg, ex, batch=1, seed=0)
    assert max(clean.values()) < 1e-4
    assert max(bad.values()) > 5 * smoke.ATTN_TOL


def test_logits_check_catches_a_dropped_block(smoke):
    """A prefill plan that leaves one block of the last row out, in one
    layer, fails LOGITS_TOL; the covering plan passes it."""
    import jax
    from repro.launch.steps import causal_band_tables
    from repro.models.registry import build
    cfg = _fused(get_config("qwen2-7b").reduced(), 16)
    params = jax.jit(build(cfg).init)(jax.random.key(0))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 70)
    plan = causal_band_tables(cfg.num_layers, 5)
    clean = smoke.prefill_agreement(cfg, params, plan, prompt)
    bad = smoke.prefill_agreement(cfg, params, dict(
        plan, nvalid=_drop_one_block(plan["nvalid"], 0)), prompt)
    assert clean["worst_block_rel_l2"] < smoke.LOGITS_TOL
    assert bad["worst_block_rel_l2"] > 3 * smoke.LOGITS_TOL
    # one block's error is diluted in the whole-prompt norm
    assert bad["worst_block_rel_l2"] > bad["rel_l2"]


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_alone_without_the_repo_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_mesh_phase_matches_one_device():
    """The --chips 4 phase on 4 virtual CPU devices: both meshes shard the
    fused (interpreted) kernel and agree with the meshless step."""
    from conftest import run_subprocess_case
    out = run_subprocess_case("""
import dataclasses, importlib.util
from repro.configs import get_config
from repro.launch.mesh import make_mesh, make_seq_mesh
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
cfg = get_config("spion-lra")
cfg = cfg.replace(spion=dataclasses.replace(cfg.spion, kernel="fused",
                                            block_size=16))
meshes = [("data2_model2", make_mesh((2, 2), ("data", "model"))),
          ("seq2_data2", make_seq_mesh(2, 2))]
fs = smoke.mesh_phase(cfg, meshes, seq_len=128, batch=4)
assert [f["mesh"] for f in fs] == ["data2_model2", "seq2_data2"]
assert all(f["shard_map"] and not f["tpu_custom_call"] for f in fs)
print("OK")
""")
    assert "OK" in out
