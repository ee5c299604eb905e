"""The engine's span records on a device trace's clock (bench/spans.py)
and the five admission and decode readers, on events made by hand: a
serving loop whose ticks admit now and then and always decode, its records
on `time.time_ns()`, its trace with the device clock planted off the
host's."""
import sys
import time

import numpy as np
import pytest

import bench_cases  # noqa: F401  (puts the repository on the path)
from bench import harness, spec, spans as S
from bench import trace as T

BASE_NS = 1_792_308_709_143_008_122     # the profile's start on time.time_ns
MS = 1e-3
READERS = ("decode_host_idle_share", "decode_tick_ms",
           "admit_host_idle_share", "admit_stall_p95_ms",
           "admit_host_idle_share.sat")


def loop(ticks, delta, *, margins=(0.5 * MS, 0.5 * MS), admit_every=4,
         ops_per_tick=1, early=None, seed=0):
    """(trace, records, truth) of `ticks` serving ticks. On the host clock
    (the trace's; records are it plus BASE_NS): a tick admits (every
    `admit_every`-th, 30-70 ms, the prefill busy on the device all but 1
    ms at each end), then decodes: the launch, the decode program starting
    `margins[0]` after the launch starts (plus up to 0.8 ms, with
    `seed`), 15 ms long, the argmax, the fetch ending `margins[1]` after
    the program ends (plus up to 0.7 ms). The device's events sit `delta`
    seconds off the host's. `early`: a tick whose program starts 3 ms
    before its launch."""
    rng = np.random.default_rng(seed)
    jitter = seed != 0
    host, ops, mods, recs = [], [], [], []
    truth = {"decode": [], "admit": [], "busy": []}

    def rec(name, a, b, **ids):
        recs.append((name, ids, BASE_NS + int(round(a * 1e9)),
                     BASE_NS + int(round(b * 1e9))))

    def dev(name, a, b, module=None):
        truth["busy"].append((a, b))
        if module:
            mods.append(T.Event(module, a + delta, b - a))
        for i in range(ops_per_tick if module else 1):
            w = (b - a) / (ops_per_tick if module else 1)
            ops.append(T.Event(name, a + i * w + delta, w))

    # warm-up decodes before the trace starts: records only
    for k in range(5):
        rec("serve.decode.launch", 0.001 + k * 0.004, 0.0012 + k * 0.004)
        rec("serve.decode.fetch", 0.002 + k * 0.004, 0.003 + k * 0.004)
        rec("serve.decode", 0.001 + k * 0.004, 0.0031 + k * 0.004, active=1)
    t = 0.1
    for k in range(ticks):
        t0 = t
        t += 5e-6
        if k % admit_every == 0:
            dur = (30 + 40 * rng.random()) * MS
            dev("%fusion.1 = bf16[1] fusion()", t + MS, t + dur - MS,
                "jit_prefill(7)")
            rec("serve.admit", t, t + dur, rid=k, slot=0, prompt_len=900)
            truth["admit"].append((t, t + dur))
            t += dur + 5e-6
        d0 = t
        la = (d0 + 0.1 * MS, d0 + 0.3 * MS)
        s = la[0] + margins[0] + jitter * 0.8 * MS * rng.random()
        if k == early:
            s = la[0] - 3 * MS
        e = s + 15 * MS
        dev("%fusion.2 = bf16[1] fusion()", s, e, "jit_serve_step(9)")
        dev("%argmax.3 = s32[1] reduce()", e + 0.1 * MS, e + 0.3 * MS)
        fe = (max(la[1], e) + 0.05 * MS,
              e + margins[1] + jitter * 0.7 * MS * rng.random())
        d1 = fe[1] + 0.05 * MS
        rec("serve.decode.launch", *la)
        rec("serve.decode.fetch", *fe)
        rec("serve.decode", d0, d1, active=3)
        truth["decode"].append((d0, d1))
        t = d1 + 3e-6
        host.append(T.Event("bench.tick", t0, t - t0))
        t += 20e-6
    window = (0.05, t + 0.01)
    host.append(T.Event("bench.window", window[0], window[1] - window[0]))
    # the drain after the trace: records only
    for k in range(3):
        a = t + 0.05 + k * 0.02
        rec("serve.decode.launch", a, a + 0.001)
        rec("serve.decode.fetch", a + 0.002, a + 0.015)
        rec("serve.decode", a, a + 0.016, active=1)
    ops.sort(key=lambda x: x.start)
    return T.DeviceTrace(window, [ops], [mods], host), recs, truth


def idle_inside(spans_, busy, lo, hi):
    """Seconds of `spans_` in which nothing in `busy` runs (one span at a
    time against every busy interval)."""
    total = 0.0
    for a, b in spans_:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        cut = sorted((max(x, a), min(y, b)) for x, y in busy
                     if y > a and x < b)
        covered, end = 0.0, a
        for x, y in cut:
            if y > end:
                covered += y - max(x, end)
                end = y
        total += (b - a) - covered
    return total


@pytest.mark.parametrize("delta_ms", [0.0, 1.5, -1.5])
def test_fit_recovers_a_planted_offset(delta_ms):
    trace, recs, _ = loop(60, delta_ms * MS, seed=3)
    f = S.fit(trace, recs)
    assert f is not None and f.ticks == 60
    # host: the records count from their first start, 1 ms after the
    # profile's; a tick ends 3 us after its decode
    first_ns = min(r[2] for r in recs)
    assert f.host == pytest.approx((first_ns - BASE_NS) * 1e-9, abs=5e-6)
    assert abs((f.device - f.host) - delta_ms * MS) < 0.25 * MS
    assert f.bracket[0] <= f.device - f.host <= f.bracket[1]
    assert S.outside_ticks(trace, recs, f.host, f.base_ns) == (1.0, 0.0)


def test_no_fit_on_an_empty_bracket():
    trace, recs, _ = loop(40, 1.5 * MS, early=17)
    assert S.fit(trace, recs) is None
    assert S.reduce(trace, recs) == (None, None)


def _run(trace):
    return harness.Run(cell=None, peaks=bench_cases.PEAKS, trace=trace)


def test_no_reading_without_records(monkeypatch):
    trace, recs, _ = loop(20, 0.0)
    assert S.reduce(trace, []) == (None, None)
    # a program without the recorder: the readers read nothing, and raise
    # nothing
    monkeypatch.setitem(sys.modules, "repro.launch.spans", None)
    assert S.records() == []
    run = _run(trace)
    assert all(spec.metric_reader(m)(run) is None for m in READERS)
    assert S.reading(_run(None), "decode_tick_ms") is None


def test_each_reader_reads_its_value(monkeypatch):
    trace, recs, truth = loop(50, -0.7 * MS, admit_every=3)
    monkeypatch.setattr(S, "records", lambda: recs)
    run = _run(trace)
    got = {m: spec.metric_reader(m)(run) for m in READERS}
    lo, hi = trace.window
    win = hi - lo
    busy = truth["busy"]
    decodes = truth["decode"]
    want_stalls, prev = [], -1.0
    for a, b in decodes:
        want_stalls.append(sum(y - x for x, y in truth["admit"]
                               if prev <= x < a))
        prev = b
    want = {
        "decode_host_idle_share": 100 * idle_inside(decodes, busy, lo, hi)
        / win,
        "admit_host_idle_share": 100 * idle_inside(truth["admit"], busy, lo,
                                                   hi) / win,
        "decode_tick_ms": 1e3 * float(np.median([b - a for a, b in decodes])),
        "admit_stall_p95_ms": 1e3 * harness.nearest_rank(want_stalls, 0.95),
    }
    want["admit_host_idle_share.sat"] = want["admit_host_idle_share"]
    # each decode leaves about 0.95 ms idle around its 15-ms program, each
    # admission 2 ms around its prefill
    assert 2 < want["decode_host_idle_share"] < 10
    assert 1 < want["admit_host_idle_share"] < 5
    assert want["admit_stall_p95_ms"] > 30
    for m in READERS:
        assert got[m] == pytest.approx(want[m], rel=1e-6), m


def test_reduction_of_a_long_trace_is_one_sweep():
    """About 350k device operations and 5k spans, reduced in under 10 s."""
    trace, recs, _ = loop(1700, 1.0 * MS, ops_per_tick=200, admit_every=20,
                          seed=5)
    assert len(trace.ops[0]) > 340_000 and len(recs) > 5000
    t = time.perf_counter()
    vals, f = S.reduce(trace, recs)
    assert time.perf_counter() - t < 10.0
    assert f is not None and f.ticks == 1700
    assert all(v is not None and np.isfinite(v) for v in vals.values())
