"""A cell finds its model family, driver, batches and mesh by name: a new
family and a new task are new files only; a training cell on the mesh
its traffic names, its check and its faults on four virtual CPU devices;
and the shares that count chips."""
import filecmp
import json
import os
import shutil

import numpy as np
import pytest
from bench_cases import PEAKS, ROOT, run_on_cpu_devices, small_train_cell

from bench import harness, spec
from bench import trace as T

STUB_FAMILY = '''"""A family found by name: the dense family's parts."""
from bench import spec

dense = spec.part("families", "dense_attention")
CALLS = []
PROGRAM_FIELDS = dense.PROGRAM_FIELDS
program_dims = dense.program_dims
visible = dense.visible
train_flops_per_step = dense.train_flops_per_step


def dims(conf):
    return dense.dims(conf)


def train_steps(*a, **k):
    CALLS.append("train_steps")
    return dense.train_steps(*a, **k)
'''

STUB_TASK = '''"""A task found by name: rows counting up from the seed."""
import numpy as np


def batches(seed, traffic, *, count, vocab_size):
    B, L = traffic["batch"], traffic["seq_len"]
    out = []
    for i in range(count):
        x = (np.arange(B * (L + 1)).reshape(B, L + 1) * 7 + seed + 13 * i)
        x = (x % (vocab_size - 1)).astype(np.int32)
        out.append({"tokens": x[:, :-1], "labels": x[:, 1:]})
    return out
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_a_new_family_and_task_are_new_files_only(tmp_path, monkeypatch):
    """A copy of bench/ with a stub family, a stub task, a configuration,
    a traffic mix, limits and entries in BENCHMARK.json added: spec.cell,
    the train driver's build and the check find every part by name, and
    no file that bench/ had is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = spec.benchmark()
    _write(str(root / "bench/families/stub_family.py"), STUB_FAMILY)
    _write(str(root / "bench/tasks/stub_task.py"), STUB_TASK)
    conf = dict(spec.read_json("bench/configs/spion-lra.json"),
                name="stub", family="stub_family", num_hidden_layers=2,
                spion=dict(spec.read_json("bench/configs/spion-lra.json")[
                    "spion"], block_size=16))
    _write(str(root / "bench/configs/stub.json"), json.dumps(conf))
    mix = dict(spec.read_json("bench/traffic/listops-sparse.json"),
               task="stub_task", batch=4, seq_len=128, pool=3,
               reference_chunk=2, trace_steps=1)
    _write(str(root / "bench/traffic/stub-train.json"), json.dumps(mix))
    _write(str(root / "bench/limits/stub.stub-train.json"), json.dumps(
        {"limits": {"gnorm_rel_gap": 0.05, "delta_norm_gap": 0.05}}))
    bm["configs"].append({"name": "stub", "source": "https://example.org",
                          "file": "bench/configs/stub.json", "reduced": [],
                          "why": "a stub"})
    bm["workloads"].append({"name": "stub.stub-train", "config": "stub",
                            "traffic": "stub-train", "chips": 1,
                            "why": "a stub"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "spion-lra.listops-sparse" in m.get("workloads", []):
            m["workloads"].append("stub.stub-train")
    _write(str(root / "BENCHMARK.json"), json.dumps(bm))
    monkeypatch.setattr(spec, "ROOT", str(root))

    cell = spec.cell("stub.stub-train")
    assert cell.family.__file__.startswith(str(root))
    run = harness.Run(cell=cell, peaks=PEAKS)
    spec.driver("train").run(run, seed=5, seconds=0.2, trace=False,
                             t_process=0.0,
                             compiles=harness.CompileCounter())
    res = harness.result(run, False)
    assert res["correct"], res["checks"]
    assert cell.family.CALLS == ["train_steps"]
    first = spec.task("stub_task").batches(5, mix, count=1, vocab_size=512)
    assert np.array_equal(run.record["check_inputs"][1][0]["tokens"],
                          first[0]["tokens"])
    # every file bench/ had is as it was
    for d, _, files in os.walk(os.path.join(ROOT, "bench")):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert filecmp.cmp(os.path.join(ROOT, rel), str(root / rel),
                               shallow=False), rel


def test_parts_are_found_by_name_only():
    assert spec.driver("train") is spec.part("drivers", "train")
    with pytest.raises(KeyError):
        spec.part("families", "no_such_family")
    with pytest.raises(KeyError):
        spec.part("families", "../spec")


def test_a_mesh_that_does_not_span_the_chips_is_refused():
    train = spec.driver("train")
    assert train.cell_mesh({"batch": 2}, 1) is None
    with pytest.raises(ValueError):
        train.cell_mesh({"mesh": {"seq": 2, "data": 2}}, 2)
    with pytest.raises(ValueError):
        train.cell_mesh({"mesh": {"seq": 2}}, 4)


def test_the_mesh_comes_from_the_traffic_on_four_devices():
    out = run_on_cpu_devices("""
import json
import numpy as np
from bench import spec
from repro.configs import get_config
train = spec.driver("train")
mesh = train.cell_mesh({"mesh": {"seq": 2, "data": 2}}, 4)
b = train.place_batch(get_config("qwen2-7b").replace(num_layers=1), mesh,
                      {"tokens": np.zeros((2, 64), np.int32)})
print(json.dumps({"shape": dict(mesh.shape),
                  "shard": list(b["tokens"].sharding.shard_shape((2, 64))),
                  "devices": len(b["tokens"].sharding.device_set)}))
""")
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"shape": {"seq": 2, "data": 2}, "shard": [1, 32],
                   "devices": 4}


def test_a_training_cell_on_a_mesh_of_four_devices():
    """The sparse training cell at a CPU size on a (seq 2, data 2) mesh of
    four virtual devices: the program on the mesh passes the sound runs'
    limits against the one-device reference, and the half-batch fault and
    the int8 control fail the cell's committed limits."""
    out = run_on_cpu_devices("""
import json
from bench import spec
from bench_cases import drive, small_train_cell
from test_bench_drivers import TRAIN_LIMITS
train = spec.driver("train")
made, cell_mesh = [], train.cell_mesh
train.cell_mesh = lambda t, chips: made.append(cell_mesh(t, chips)) or made[-1]
c = small_train_cell("sparse")
committed = c.limits["limits"]
c.chips = 4
c.traffic = dict(c.traffic, mesh={"seq": 2, "data": 2})
c.limits = {"limits": TRAIN_LIMITS}
run, res = drive(c, control=True)
print(json.dumps({"correct": res["correct"], "committed": committed,
                  "mesh": [dict(m.shape) for m in made],
                  "readings": run.record["readings"]}))
""")
    got = json.loads(out.strip().splitlines()[-1])
    r = got["readings"]
    assert got["mesh"] == [{"seq": 2, "data": 2}]
    assert got["correct"], r
    for name in ("control_int8", "fault_half_batch"):
        assert not harness.passes([(k, r[f"{name}.{k}"], v)
                                   for k, v in got["committed"].items()]), name


class FakeChip:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


@pytest.mark.parametrize("chips,want", [(1, 5), (4, 9)])
def test_memory_peak_is_the_fullest_of_the_cells_chips(chips, want,
                                                      monkeypatch):
    chips_there = [FakeChip(5), FakeChip(None), FakeChip(9), FakeChip(7),
                   FakeChip(11)]
    monkeypatch.setattr(harness.jax, "devices", lambda: chips_there)
    assert harness.memory_peak(chips) == want


@pytest.mark.parametrize("chips", [1, 4])
def test_whole_step_and_kernel_shares_count_chips(chips):
    cell = small_train_cell("sparse")
    cell.chips = chips
    run = harness.Run(cell=cell, peaks=PEAKS)
    run.block, run.plan_nvalid = 16, [np.full(8, 2)] * 2
    run.record = {"steps": 10, "window_s": 2.0, "batch": 4, "seq_len": 128}
    per_step = cell.family.train_flops_per_step(
        run.model, run.plan_nvalid, batch=4, seq_len=128, block=16)
    mfu = spec.metric_reader("train_mfu")(run)
    assert mfu == pytest.approx(100.0 * per_step * 5 /
                                (chips * PEAKS["flops_bf16"]))
    kernel = ("%fused_dq.1 = f32[8,1,128,128]{3,2,1,0} custom-call(%a), "
              'custom_call_target="tpu_custom_call"')
    run.trace = T.DeviceTrace((0.0, 1.0), [[T.Event(kernel, 0.0, 0.01)]
                                           for _ in range(chips)],
                              [[]] * chips, [])
    one = spec.metric_reader("fused_dq_roofline")(run)
    # each chip ran its 1/chips share of the same call in the same time:
    # the share is that of one chip running the whole call alone
    from bench import flops
    f, b = flops.fused_dq(batch=4, heads=4, hd=16, block=16, rows=8,
                          blocks=16, dsize=2)
    assert one == pytest.approx(100.0 * flops.roofline_s(f, b, PEAKS) /
                                (0.01 * chips))
