"""Each cell's driver, run on the CPU at a reduced size through the same
functions the chip runs; the faults a cell can have, planted under the
timed path, turn `correct` false; and the command refuses to run without
a TPU."""
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_cases import ROOT, drive, small_serve_cell, small_train_cell

from bench import harness, reference, train

# limits for the sound runs at these reduced sizes, between their readings
# here (loss 1e-4, gradient norm before the clip 8e-3, first gradient 2e-2,
# change 2e-3, served gap 0) and the faults' (a tenth and more); a fault
# is held to the cell's own committed limits
TRAIN_LIMITS = {"loss_rel_gap": 1e-3, "gnorm_rel_gap": 0.05,
                "grad1_norm_gap": 0.05, "delta_norm_gap": 0.05}
SERVE_LIMITS = {"served_logit_gap": 0.05}


def limited(cell, limits):
    cell.limits = {"limits": dict(limits)}
    return cell


@pytest.mark.parametrize("phase", ["sparse", "dense"])
def test_train_cell_sound_run(phase, capsys):
    run, res = drive(limited(small_train_cell(phase), TRAIN_LIMITS))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(res["checks"])[-1] == "window_compiles"
    out = capsys.readouterr().out
    assert "bench plan:" in out
    assert ("plan_digest" in out) == (phase == "sparse")


def state_unchanged(step):
    def unchanged(self, batch):
        params, opt = self.params, self.opt
        keep = (jax_copy(params), jax_copy(opt))
        metrics = step(self, batch)
        self.params, self.opt = keep
        return metrics
    return unchanged


def half_batch(step):
    def half(self, batch):
        n = batch["tokens"].shape[0] // 2
        return step(self, {k: v[:n] for k, v in batch.items()})
    return half


def plant(monkeypatch, fault):
    from repro.launch.train import Trainer
    monkeypatch.setattr(Trainer, "_one_step", fault(Trainer._one_step))


def test_train_fault_state_unchanged(monkeypatch):
    plant(monkeypatch, state_unchanged)
    run, res = drive(small_train_cell("sparse"))
    assert not res["correct"]
    assert res["checks"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


def jax_copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)


def test_train_fault_half_batch(monkeypatch):
    """Caught by the gradient's norm before the clip: the clip and Adam's
    normalised update leave the other norms only the direction."""
    plant(monkeypatch, half_batch)
    run, res = drive(small_train_cell("sparse"))
    assert not res["correct"]
    gap = res["checks"]["gnorm_rel_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_train_dense_fault_at_the_cell_limits(fault, monkeypatch):
    plant(monkeypatch, fault)
    run, res = drive(small_train_cell("dense"))
    assert not res["correct"]


@pytest.mark.parametrize("phase", ["sparse", "dense"])
def test_train_int8_control_fails_at_the_cell_limits(phase):
    """The int8 reference in the program's place is judged as a run is,
    at the cell's committed limits, and does not pass."""
    cell = small_train_cell(phase)
    run, res = drive(cell)
    r = run.record["check_inputs"]
    ctrl = train.compare(train.reference_run(run, "int8", *r),
                         train.reference_run(run, "f32", *r))
    lim = cell.limits["limits"]
    assert not harness.passes([(k, ctrl[k], lim[k]) for k in lim]), ctrl


def test_train_control_reads_above_the_program():
    """The float8 reference in the program's place reads three times the
    sound program or more on one of the numbers, on the same first
    steps (at this size, the loss)."""
    cell = limited(small_train_cell("sparse"), TRAIN_LIMITS)
    run, res = drive(cell)
    prog = {k: v["value"] for k, v in res["checks"].items()}
    r = run.record["check_inputs"]
    f32 = train.reference_run(run, "f32", *r)
    fp8 = train.reference_run(run, "fp8", *r)
    ctrl = train.compare(fp8, f32)
    assert any(ctrl[k] >= 3 * prog[k] for k in TRAIN_LIMITS), (ctrl, prog)


def test_serve_cell_sound_run():
    run, res = drive(limited(small_serve_cell(), SERVE_LIMITS))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 4
    assert {"serve_output_tokens_per_s", "itl_p95_ms",
            "setup_s"} <= set(res["metrics"])
    assert run.record["readings"]["sampled_tokens"] >= 1


def test_serve_fault_token_altered(monkeypatch):
    from repro.launch.serve import ServeEngine
    tick = ServeEngine._decode_tick

    def altered(self):
        tick(self)
        for r in self.active:
            if r is not None:
                r.out[-1] = (r.out[-1] + 1) % self.cfg.vocab_size
    monkeypatch.setattr(ServeEngine, "_decode_tick", altered)
    run, res = drive(limited(small_serve_cell(), SERVE_LIMITS))
    assert not res["correct"]


def test_serve_control_and_fault_readings():
    run, res = drive(limited(small_serve_cell(), SERVE_LIMITS),
                              control=True)
    r = run.record["readings"]
    assert r["control_fp8.served_logit_gap"] > max(
        3 * r["served_logit_gap"], SERVE_LIMITS["served_logit_gap"])
    assert r["fault_token_altered.served_logit_gap"] > \
        SERVE_LIMITS["served_logit_gap"]


def test_a_compile_in_the_window_fails_the_run(monkeypatch):
    from bench import serve
    monkeypatch.setattr(serve, "warm", lambda *a, **k: [])
    run, res = drive(limited(small_serve_cell(), SERVE_LIMITS))
    assert res["checks"]["window_compiles"]["value"] > 0
    assert not res["correct"]


def test_reference_softmax_counts_pruned_positions():
    """A row that lists one block of two sees exp(0) for each left-out
    position in its denominator; all listed, it is the plain softmax."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 8, 2, 4)), jnp.float32)
               for _ in range(3))
    full, tot = reference.visible(np.ones((2, 2), bool), 8, False)
    out = reference.attention(reference.einsum_f32, q, k, v, full, tot)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 2.0
    want = jnp.einsum("bhqk,bkhd->bqhd", jax_softmax(s), v)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    diag, tot = reference.visible(np.eye(2, dtype=bool), 8, False)
    out = reference.attention(reference.einsum_f32, q, k, v, diag, tot)
    e = jnp.where(diag, jnp.exp(s), 0.0)
    p = e / (e.sum(-1, keepdims=True) + 4.0)
    np.testing.assert_allclose(out, jnp.einsum("bhqk,bkhd->bqhd", p, v),
                               rtol=1e-5, atol=1e-6)


def jax_softmax(x):
    import jax
    return jax.nn.softmax(x, -1)


def test_command_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "spion-lra.listops-sparse", "--seed",
                        "3", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=tmp_path,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_cache_load_in_the_window_counts():
    """A program loaded from the persistent cache inside the window was
    missing from the warm-up, as a compile is."""
    import jax
    from bench import harness
    counter = harness.CompileCounter()
    jax.monitoring.record_event(harness.CompileCounter.CACHE_HIT)
    assert counter.count == 0
    counter.armed = True
    jax.monitoring.record_event(harness.CompileCounter.CACHE_HIT)
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert counter.count == 1


@pytest.mark.parametrize("make", [lambda: small_train_cell("sparse"),
                                  small_serve_cell],
                         ids=["train", "serve"])
def test_every_seed_runs_under_the_same_plan(make, capsys):
    """The plan comes from the mix's plan seed, so two run seeds do the
    same work; another plan seed gives another plan."""
    import json

    def digest(cell, seed):
        drive(limited(cell, {}), seed=seed, seconds=0.2)
        line = [x for x in capsys.readouterr().out.splitlines()
                if x.startswith("bench plan:")][-1]
        return json.loads(line[len("bench plan:"):])["plan_digest"]
    a, b = digest(make(), 2**31 + 1), digest(make(), 7)
    assert a == b
    other = make()
    other.traffic = dict(other.traffic, plan_seed=1)
    assert digest(other, 7) != a
