"""The reduction from a profiler trace to kernel times, busy time and idle
share: on events made by hand, and on a trace recorded on a TPU v5e (two
sparse training steps of spion-lra, ListOps L=2048, batch 128)."""
import os

import pytest

import bench_cases  # noqa: F401  (puts the repository on the path)
from bench import trace as T

FWD = ('%_dispatch.34 = (bf16[512,1,2048,128]{3,2,1,0}, f32[512,1,2048,1]'
       '{3,2,1,0}) custom-call(s32[32,3]{1,0} %a), '
       'custom_call_target="tpu_custom_call"')
DQ = ('%_dispatch.37 = f32[512,1,2048,128]{3,2,1,0} custom-call(s32[32,3] '
      '%a), custom_call_target="tpu_custom_call"')
DKV = ('%_dispatch.36 = (f32[512,2048,128]{2,1,0}, f32[512,2048,128]{2,1,0})'
       ' custom-call(s32[32,22] %a), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %p), kind=kLoop"
ALLOC = ('%custom-call.4 = bf16[4,128]{1,0} custom-call(), '
         'custom_call_target="AllocateBuffer"')
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "train_sparse_2steps.xplane.pb")


def ev(name, start, dur):
    return T.Event(name, start, dur)


def test_kernel_kind_by_signature():
    assert T.kernel_kind(FWD) == "fused_fwd"
    assert T.kernel_kind(DQ) == "fused_dq"
    assert T.kernel_kind(DKV) == "fused_dkv"
    assert T.kernel_kind(FUSION) is None and T.kernel_kind(ALLOC) is None
    assert T.module_of("jit_prefill(1234)") == "jit_prefill"


def made_by_hand():
    loop = "%while.7 = (s32[]) while(s32[] %x), condition=%c, body=%b"
    ops = [ev(loop, 1.0, 4.0), ev(FWD, 1.0, 1.0), ev(DQ, 2.5, 0.5),
           ev(DKV, 3.0, 1.5), ev(FUSION, 6.0, 1.0), ev(FWD, 9.5, 1.0)]
    mods = [ev("jit_with_tables(1)", 1.0, 6.0), ev("jit_prefill(2)", 9.5, 1)]
    host = [ev("bench.window", 0.0, 10.0), ev("bench.step", 0.5, 7.0),
            ev("bench.wait", 7.5, 2.0)]
    return T.DeviceTrace((0.0, 10.0), [ops], [mods], host)


def test_busy_is_a_union_clipped_to_the_window():
    t = made_by_hand()
    # [1, 5] (the loop holds its body), [6, 7], [9.5, 10] (clipped)
    assert t.busy_s() == pytest.approx(4.0 + 1.0 + 0.5)
    assert t.window_s == 10.0


def test_kernel_and_program_times():
    t = made_by_hand()
    k = t.kernel_times()
    assert k["fused_fwd"] == (2, pytest.approx(2.0))
    assert k["fused_dq"] == (1, 0.5) and k["fused_dkv"] == (1, 1.5)
    assert t.module_times()["jit_prefill"] == (1, 1)
    inside = t.kernel_times_in("jit_with_tables")
    assert inside["fused_fwd"] == (1, 1.0) and "fused_dkv" in inside
    assert t.kernel_times_in("jit_prefill")["fused_fwd"][0] == 1


def test_top_ops_and_idle_gaps():
    t = made_by_hand()
    top = dict(t.top_ops(10))
    assert "fused_dkv:_dispatch.36" in top and not any(
        k.startswith("while") for k in top)
    gaps = t.idle_gaps(10)
    # [7, 9.5] while the host waited, [0, 1] and [5, 6] inside the step
    assert gaps[0] == ["bench.wait", pytest.approx(2.5)]
    assert sorted(g[0] for g in gaps[1:]) == ["bench.step", "bench.step"]


def test_recorded_chip_trace():
    ops, mods, host = T.planes(DATA)
    assert len(ops) == len(mods) == 1 and not host
    steps = [e for e in mods[0] if T.module_of(e.name) == "jit_with_tables"]
    assert len(steps) == 2
    window = (steps[0].start, steps[-1].start + steps[-1].dur)
    t = T.DeviceTrace(window, ops, mods, host)
    # without the harness's window span, load() takes the programs' extent
    lo, hi = T.load(DATA).window
    assert lo <= window[0] < window[1] <= hi < lo + 2.5
    k = t.kernel_times()
    # two steps of 4 layers: the forward twice a layer (remat), dQ and
    # dK/dV once
    assert {n: c for n, (c, _) in k.items()} == {"fused_fwd": 16,
                                                 "fused_dq": 8, "fused_dkv": 8}
    assert k["fused_dkv"][1] == pytest.approx(1.4164, rel=1e-3)
    assert k["fused_fwd"][1] == pytest.approx(0.5327, rel=1e-3)
    assert k["fused_dq"][1] == pytest.approx(0.2234, rel=1e-3)
    # the step program is device-bound: busy all but a few ms of 2.36 s
    assert t.window_s == pytest.approx(2.3658, rel=1e-3)
    assert t.busy_s() == pytest.approx(2.3622, rel=1e-3)
    idle = 1 - t.busy_s() / t.window_s
    assert 1e-3 < idle < 3e-3
    assert t.top_ops(1)[0][0].startswith("fused_dkv:")
    assert sum(s for _, s in k.values()) < t.busy_s()
