"""Self-healing training (DESIGN.md §13), tier-1 half.

Two layers under test:

  1. The external FleetSupervisor — classify() verdicts from synthetic
     heartbeat payloads, and the spawn/watch/kill/respawn loop driven with
     real (but jax-free, millisecond-scale) subprocess workers.
  2. The in-loop divergence sentinel — a REAL tiny Trainer run where a
     chaos-injected NaN step triggers quarantine + restore of the pinned
     good checkpoint + data-window skip, and the stitched post-rollback
     losses match a run that never saw the poisoned batch.

The multi-process versions (2 real jax.distributed workers under the
supervisor, hang/kill/NaN legs) live in test_multiprocess.py behind
SPION_MP_TESTS=1.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

from repro.distributed.supervisor import (FleetSupervisor, StepTracker,
                                          classify, free_port)


# -- classify: the liveness verdict --------------------------------------------

def test_classify_healthy_and_dead():
    tr = StepTracker()
    hb = {"ts": 100.0, "step": 5}
    assert classify(101.0, 90.0, hb, tr, dead_timeout=10.0,
                    hang_timeout=60.0) is None
    # stale ts -> dead, regardless of step history
    assert classify(111.0, 90.0, hb, tr, dead_timeout=10.0,
                    hang_timeout=60.0) == "dead"


def test_classify_missing_payload_counts_from_spawn():
    tr = StepTracker()
    # no heartbeat yet: grace window runs from spawn time, not from epoch 0
    assert classify(105.0, 100.0, None, tr, dead_timeout=10.0,
                    hang_timeout=60.0) is None
    assert classify(111.0, 100.0, None, tr, dead_timeout=10.0,
                    hang_timeout=60.0) == "dead"


def test_classify_hang_requires_frozen_step_with_fresh_ts():
    tr = StepTracker()
    # step advancing: never hung
    assert classify(10.0, 0.0, {"ts": 10.0, "step": 1}, tr,
                    dead_timeout=60.0, hang_timeout=5.0) is None
    assert classify(14.0, 0.0, {"ts": 14.0, "step": 2}, tr,
                    dead_timeout=60.0, hang_timeout=5.0) is None
    # frozen step, fresh ts (the beat thread still runs): hung after timeout
    assert classify(18.0, 0.0, {"ts": 18.0, "step": 2}, tr,
                    dead_timeout=60.0, hang_timeout=5.0) is None
    assert classify(20.0, 0.0, {"ts": 20.0, "step": 2}, tr,
                    dead_timeout=60.0, hang_timeout=5.0) == "hung"


def test_classify_hang_arms_only_after_first_step():
    """Before the worker publishes any step the payload is indistinguishable
    from a long first-step jit compile — the hang watchdog must NOT fire."""
    tr = StepTracker()
    for now in (10.0, 100.0, 1000.0):
        assert classify(now, 0.0, {"ts": now}, tr, dead_timeout=1e9,
                        hang_timeout=5.0) is None
    assert classify(1001.0, 0.0, {"ts": 1001.0, "step": 1}, tr,
                    dead_timeout=1e9, hang_timeout=5.0) is None
    assert classify(1010.0, 0.0, {"ts": 1010.0, "step": 1}, tr,
                    dead_timeout=1e9, hang_timeout=5.0) == "hung"


def test_classify_straggler_limit():
    tr = StepTracker()
    hb = {"ts": 10.0, "step": 3, "stragglers": 7}
    assert classify(11.0, 0.0, hb, tr, dead_timeout=60.0,
                    hang_timeout=60.0) is None  # off by default
    assert classify(11.0, 0.0, hb, tr, dead_timeout=60.0, hang_timeout=60.0,
                    straggler_limit=8) is None
    assert classify(11.0, 0.0, hb, tr, dead_timeout=60.0, hang_timeout=60.0,
                    straggler_limit=7) == "straggler"


@pytest.mark.parametrize("platforms,nproc,chips,ok", [
    ("", 2, 4, False),       # two workers, one TPU lock
    ("tpu", 2, 1, False),
    ("cpu", 2, 4, True),     # workers never touch the TPU
    ("", 1, 4, True),        # one worker drives every local chip
    ("", 2, 0, True),        # not a TPU host
])
def test_supervisor_one_worker_per_tpu_host(monkeypatch, platforms, nproc,
                                            chips, ok):
    """On a TPU host the fleet is one worker: a chip belongs to one process."""
    from repro.distributed import supervisor
    monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: chips)
    env = {"JAX_PLATFORMS": platforms} if platforms else {}
    make = lambda: FleetSupervisor(["true"], nproc, "/tmp/x",  # noqa: E731
                                   env=env)
    if ok:
        assert make().nproc == nproc
    else:
        with pytest.raises(RuntimeError, match="one worker per host"):
            make()


def test_local_tpu_chips_counts_tpu_pci_ids_only(tmp_path):
    """Only Google-vendor devices with a TPU device id count: a gVNIC card
    (same vendor) and other vendors' devices do not."""
    from repro.distributed.supervisor import local_tpu_chips
    for name, vendor, device in [("a", "0x1ae0", "0x0063"),   # v5e chip
                                 ("b", "0x1ae0", "0x0063"),
                                 ("c", "0x1ae0", "0x0042"),   # gVNIC
                                 ("d", "0x8086", "0x0063")]:
        d = tmp_path / name
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    assert local_tpu_chips(str(tmp_path)) == 2
    assert local_tpu_chips(str(tmp_path / "missing")) == 0


def test_supervisor_backoff_capped():
    sup = FleetSupervisor(["true"], 1, "/tmp/x", backoff_base=1.0,
                          backoff_max=5.0)
    assert [sup.backoff(i) for i in range(5)] == [1.0, 2.0, 4.0, 5.0, 5.0]


# -- the respawn loop with real subprocess workers ----------------------------

def _mk_sup(tmp_path, code, nproc=1, **kw):
    kw.setdefault("poll_interval", 0.05)
    kw.setdefault("backoff_base", 0.01)
    kw.setdefault("backoff_max", 0.05)
    logs = []
    sup = FleetSupervisor([sys.executable, "-c", code], nproc, str(tmp_path),
                          log=logs.append, **kw)
    return sup, logs


def test_supervisor_clean_completion(tmp_path):
    """All workers exit 0 -> run() returns 0, no respawns; each worker saw
    its own SPION_PROCESS_ID/SPION_NUM_PROCESSES (written to marker files)."""
    code = ("import os\n"
            "d = os.environ['SPION_CKPT']\n"
            "i = os.environ['SPION_PROCESS_ID']\n"
            "n = os.environ['SPION_NUM_PROCESSES']\n"
            "open(os.path.join(d, 'saw_' + i), 'w').write(n)\n")
    sup, logs = _mk_sup(tmp_path, code, nproc=2)
    sup.env["SPION_CKPT"] = str(tmp_path)
    assert sup.run() == 0
    assert sup.respawns == 0
    assert (tmp_path / "saw_0").read_text() == "2"
    assert (tmp_path / "saw_1").read_text() == "2"
    assert any("SUPERVISOR done" in line for line in logs)


def test_supervisor_respawns_until_budget_exhausted(tmp_path):
    sup, logs = _mk_sup(tmp_path, "raise SystemExit(3)", max_respawns=2)
    assert sup.run() == 1
    assert sup.respawns == 2 and sup.generation == 2
    assert sum("SUPERVISOR fault" in line for line in logs) == 3
    assert any("exit=3" in line for line in logs)
    assert any("SUPERVISOR giveup" in line for line in logs)


def test_supervisor_respawn_heals_transient_crash(tmp_path):
    """Worker crashes in generation 0, succeeds in generation 1 (state via a
    marker file — the checkpoint-resume analogue at unit scale). The marker
    is fleet-wide: a worker killed with its crashed peer before it wrote a
    marker of its own must not crash again in generation 1."""
    code = ("import os\n"
            "m = os.path.join(os.environ['SPION_CKPT'], 'gen0')\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').close()\n"
            "    raise SystemExit(1)\n")
    sup, logs = _mk_sup(tmp_path, code, nproc=2, max_respawns=3)
    sup.env["SPION_CKPT"] = str(tmp_path)
    assert sup.run() == 0
    assert sup.respawns == 1
    assert any("SUPERVISOR respawn gen=1" in line for line in logs)


def test_supervisor_detects_silent_death(tmp_path):
    """A worker that never heartbeats (sleeps forever) is declared dead
    after dead_timeout and the fleet is torn down."""
    sup, logs = _mk_sup(tmp_path, "import time; time.sleep(600)",
                        dead_timeout=0.4, hang_timeout=600.0, max_respawns=0)
    t0 = time.time()
    assert sup.run() == 1
    assert time.time() - t0 < 60  # did not wait out the sleep
    assert any("dead" in line for line in logs if "fault" in line)
    assert sup._procs == []  # fleet reaped


def test_supervisor_detects_hang_via_frozen_step(tmp_path):
    """A worker whose beat thread keeps ts fresh but whose step counter
    never advances is 'hung' — the verdict liveness-only monitoring cannot
    reach."""
    code = (
        "import json, os, time\n"
        "p = os.path.join(os.environ['SPION_CKPT'],\n"
        "                 'hb_' + os.environ['SPION_PROCESS_ID'])\n"
        "while True:\n"
        "    open(p + '.tmp', 'w').write(\n"
        "        json.dumps({'ts': time.time(), 'step': 4}))\n"
        "    os.replace(p + '.tmp', p)\n"
        "    time.sleep(0.05)\n")
    sup, logs = _mk_sup(tmp_path, code, dead_timeout=600.0, hang_timeout=0.4,
                        max_respawns=0)
    sup.env["SPION_CKPT"] = str(tmp_path)
    t0 = time.time()
    assert sup.run() == 1
    assert time.time() - t0 < 60
    assert any("hung" in line for line in logs if "fault" in line)


def test_supervisor_clears_stale_heartbeats_between_generations(tmp_path):
    """Generation N's dying heartbeat (old ts) must not read as an instant
    fault for generation N+1."""
    stale = tmp_path / "hb_0"
    with open(stale, "w") as f:
        json.dump({"ts": 1.0, "step": 99}, f)
    sup, _ = _mk_sup(tmp_path, "pass", dead_timeout=600.0)
    assert sup.run() == 0
    assert sup.respawns == 0


# -- divergence sentinel + rollback on a real (tiny) Trainer -------------------

def _cfg():
    from repro.configs import get_config
    from repro.configs.base import SpionConfig
    return get_config("spion-lra").replace(
        num_layers=2, d_ff=64, vocab_size=64,
        spion=SpionConfig(enabled=True, variant="cf", conv_filter_size=5,
                          block_size=16, alpha_quantile=0.85,
                          transition_tol=1e9, min_dense_epochs=1,
                          max_dense_epochs=2, kernel="jnp"))


def _data_fn(batch=4, seq=32, vocab=64):
    def fn(step):
        rng = np.random.default_rng(88_000 + step)
        toks = rng.integers(0, vocab, size=(batch, seq + 1))
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}
    return fn


def _trainer(tmp_path, name, **kw):
    from repro.distributed.fault import DivergenceSentinel
    from repro.launch.train import Trainer
    kw.setdefault("sentinel", DivergenceSentinel(spike=False))
    return Trainer(_cfg(), seq_len=32, batch=4, lr=1e-3, steps_per_epoch=4,
                   ckpt_dir=str(tmp_path / name), data_fn=_data_fn(), **kw)


def test_sentinel_rollback_end_to_end(tmp_path):
    """NaN-poisoned params at step 14 (checkpoints at 5/10, poisoned save at
    15): the sentinel rolls back to the pinned good step 10, quarantines the
    poisoned step-15 save, skips the data window [10, 14], and the stitched
    losses match a reference run that never saw the poisoned batches."""
    from repro.distributed.chaos import ChaosMonkey

    tr = _trainer(tmp_path, "heal", chaos=ChaosMonkey(nan_step=14))
    tr.train(20, ckpt_every=5, log_every=10**9, log=lambda *a: None)
    assert tr.rollback_count == 1
    assert tr.data_offset == 5            # window [10, 14] skipped
    assert tr.good_step == 20
    assert tr.step == 20                  # reached the target unattended
    ev = [e for e in tr.events if e["event"] == "rollback"]
    assert len(ev) == 1 and ev[0]["from_step"] == 14 and ev[0]["to_step"] == 10
    heal_dir = tmp_path / "heal"
    # the save taken AFTER the divergence point was quarantined, then the
    # replay re-committed a healthy step 15 under the canonical name
    assert (heal_dir / "quarantined_step_000000015").exists()
    assert (heal_dir / "step_000000020").exists()

    # reference: never poisoned, data stream with the window pre-skipped
    base = _data_fn()
    ref = _trainer(tmp_path, "ref")
    ref.data_fn = lambda step: base(step if step < 10 else step + 5)
    ref.train(20, ckpt_every=5, log_every=10**9, log=lambda *a: None)

    assert sorted(tr.loss_history) == sorted(ref.loss_history) == list(range(20))
    for s in range(20):
        v, r = tr.loss_history[s], ref.loss_history[s]
        assert np.isfinite(v)
        assert abs(v - r) <= 1e-3 + 1e-3 * abs(r), (s, v, r)


def test_rollback_resume_consistency(tmp_path):
    """data_offset is persisted in the checkpoint: a process respawned
    AFTER a rollback resumes with the skip window still in effect."""
    from repro.distributed.chaos import ChaosMonkey

    tr = _trainer(tmp_path, "resume", chaos=ChaosMonkey(nan_step=7))
    tr.train(15, ckpt_every=5, log_every=10**9, log=lambda *a: None)
    assert tr.rollback_count == 1 and tr.data_offset == 3  # window [5, 7]
    tr2 = _trainer(tmp_path, "resume")
    assert tr2.maybe_resume()
    assert tr2.step == 15 and tr2.data_offset == 3
    assert tr2.good_step == 15 and tr2.ckpt.pinned() == [15]


class _AlwaysDiverge:
    def observe(self, loss):
        return True

    def reset(self):
        pass


def test_rollback_without_good_checkpoint_fails_loudly(tmp_path):
    tr = _trainer(tmp_path, "nockpt", sentinel=_AlwaysDiverge())
    with pytest.raises(RuntimeError, match="no good checkpoint"):
        tr.train(10, ckpt_every=5, log_every=10**9, log=lambda *a: None)


def test_persistent_divergence_hard_fails_after_max_rollbacks(tmp_path):
    tr = _trainer(tmp_path, "loop", max_rollbacks=2)
    tr.train(5, ckpt_every=5, log_every=10**9, log=lambda *a: None)
    assert tr.good_step == 5
    tr.sentinel = _AlwaysDiverge()
    with pytest.raises(RuntimeError, match="not recoverable"):
        tr.train(5, ckpt_every=5, log_every=10**9, log=lambda *a: None)
    assert tr.rollback_count == 3  # 2 allowed + the one that raised


def test_trainer_heartbeat_payload_reaches_supervisor_format(tmp_path):
    """The heartbeat file a Trainer writes parses into exactly what
    classify() consumes: fresh ts, advancing step, phase."""
    from repro.distributed.fault import Heartbeat

    tr = _trainer(tmp_path, "hb", heartbeat_interval=0.0)
    tr.train(3, ckpt_every=0, log_every=10**9, log=lambda *a: None)
    hb = Heartbeat.read(os.path.join(str(tmp_path / "hb"), "hb_0"))
    assert hb is not None and hb["step"] == 3
    assert hb["phase"] == tr.spion_state.phase
    assert "stragglers" in hb
    st = StepTracker()
    assert classify(hb["ts"], 0.0, hb, st, dead_timeout=60.0,
                    hang_timeout=60.0) is None
    assert st.step == 3


@pytest.mark.skipif(os.environ.get("SPION_MP_TESTS") == "1", reason="covered "
                    "by the full supervisor e2e in test_multiprocess.py")
def test_supervise_cli_rejects_missing_worker_cmd():
    from repro.launch import supervise
    with pytest.raises(SystemExit):
        supervise.main(["--nproc", "1", "--ckpt-dir", "/tmp/x"])


def test_free_port_is_bindable():
    import socket
    p = free_port()
    with socket.socket() as s:
        s.bind(("localhost", p))
