"""The serving engine's own spans on a device trace's clock, and what the
per-layer readers of admission and decode take from them.

The engine keeps its spans in memory (`repro.launch.spans.RECORD`: name,
ids, start and end on `time.time_ns()`); a trace holds the benchmark's
`bench.*` host spans and the device's operations on the trace's own clock,
whose zero is the profile's start. Two fits put the records on it.

1. Host: the ends of the records' `serve.decode` spans, in order, against
   the ends of the window's `bench.tick` spans that hold a decode (the
   midpoint of a `jit_serve_step` execution). A tick ends microseconds
   after its decode, so the run of consecutive records whose differences
   agree best (by mean absolute deviation from their median) is the
   window's, and their least difference is the host offset: every decode
   then ends inside its tick.
2. Device: the trace's device clock can stand a millisecond off its host
   clock. Each tick's `jit_serve_step` cannot start before its
   `serve.decode.launch` starts, nor end after its `serve.decode.fetch`
   ends; each tick so bounds the offset from both sides. The midpoint of
   the intersection over all ticks is taken; where it is empty, there is
   no fit, and every reader returns None.

The reduction sorts the device operations once (their idle gaps) and then
sweeps gaps and spans together.
"""
from __future__ import annotations

import dataclasses
import json
import sys

import numpy as np

from bench import harness
from bench import trace as trace_mod

DECODE_PROGRAM = "jit_serve_step"
MATCH_TICKS = 64          # ticks compared when choosing the window's records
MATCH_DEV_S = 200e-6      # a tick's end lies microseconds after its decode's
MATCH_UNIQUE = 10.0       # the best run of records agrees this much better
                          # than any other


@dataclasses.dataclass
class Fit:
    """Seconds to add to a record's time (ns since `base_ns`, over 1e9) to
    put it on the trace's host clock (`host`) and device clock (`device`)."""
    base_ns: int
    host: float
    device: float
    bracket: tuple          # (lo, hi) of device - host over all ticks
    ticks: int
    match_dev_s: float


def records():
    """The engine's span records, or [] where the program keeps none."""
    try:
        from repro.launch.spans import RECORD
    except ImportError:
        return []
    return list(RECORD)


def _seconds(recs, name, base_ns):
    """(t0, t1) in seconds since `base_ns` of the records named `name`, in
    order of start."""
    iv = sorted((r[2], r[3]) for r in recs if r[0] == name)
    return (np.asarray(iv, np.int64).reshape(-1, 2) - base_ns) * 1e-9


def decode_ticks(trace):
    """(end of the tick, start and end of its decode program) for each of
    the window's `bench.tick` spans that holds the midpoint of a
    `jit_serve_step` execution (its last such, where it holds several)."""
    lo, hi = trace.window
    ticks = sorted((h.start, h.start + h.dur) for h in trace.host
                   if h.name == "bench.tick" and h.start >= lo
                   and h.start + h.dur <= hi)
    mods = sorted((e.start, e.start + e.dur) for e in trace.modules[0]
                  if trace_mod.module_of(e.name) == DECODE_PROGRAM)
    out, i = [], 0
    for a, b in ticks:
        found = None
        while i < len(mods) and (mods[i][0] + mods[i][1]) / 2 <= b:
            if (mods[i][0] + mods[i][1]) / 2 >= a:
                found = mods[i]
            i += 1
        if found:
            out.append((b, found[0], found[1]))
    return np.asarray(out, float).reshape(-1, 3)


def _children(parents, kids):
    """Index into `kids` of the child starting inside each parent, or None
    where a parent has none."""
    idx = np.searchsorted(kids[:, 0], parents[:, 0])
    if len(kids) == 0 or (idx >= len(kids)).any():
        return None
    if (kids[idx, 0] > parents[:, 1]).any():
        return None
    return idx


def fit(trace, recs):
    """The records on the trace's clocks, or None."""
    if trace is None or not trace.modules or not recs:
        return None
    base = min(r[2] for r in recs)
    dec = _seconds(recs, "serve.decode", base)
    launch = _seconds(recs, "serve.decode.launch", base)
    fetch = _seconds(recs, "serve.decode.fetch", base)
    ticks = decode_ticks(trace)
    n, m = len(ticks), len(dec)
    if n == 0 or m < n:
        return None
    k = min(n, MATCH_TICKS)
    runs = np.lib.stride_tricks.sliding_window_view(dec[:, 1], k)[:m - n + 1]
    diff = ticks[:k, 0] - runs
    dev = np.mean(np.abs(diff - np.median(diff, 1)[:, None]), 1)
    j = int(np.argmin(dev))
    rest = np.delete(dev, j)
    if dev[j] > MATCH_DEV_S or (len(rest) and rest.min() < MATCH_UNIQUE
                                * max(dev[j], 1e-6)):
        return None
    mine = dec[j:j + n]
    host = float(np.min(ticks[:, 0] - mine[:, 1]))
    li, fi = _children(mine, launch), _children(mine, fetch)
    if li is None or fi is None:
        return None
    start = launch[li, 0] + host           # on the trace's host clock
    end = fetch[fi, 1] + host
    lo = float(np.max(ticks[:, 2] - end))
    hi = float(np.min(ticks[:, 1] - start))
    if lo > hi:
        return None
    return Fit(base, host, host + (lo + hi) / 2, (lo, hi), n, float(dev[j]))


def overlap_s(gaps, spans):
    """Seconds in both of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(gaps) and j < len(spans):
        a = max(gaps[i][0], spans[j][0])
        b = min(gaps[i][1], spans[j][1])
        if b > a:
            total += b - a
        if gaps[i][1] < spans[j][1]:
            i += 1
        else:
            j += 1
    return total


def stalls(decodes, admits):
    """Per decode tick, the seconds of admission that started since the
    previous tick ended: the stall every active slot's next token waits
    out. Both are sorted (start, end) arrays."""
    out, i = np.zeros(len(decodes)), 0
    prev = -np.inf
    for t, (a, b) in enumerate(decodes):
        while i < len(admits) and admits[i, 0] < a:
            if admits[i, 0] >= prev:
                out[t] += admits[i, 1] - admits[i, 0]
            i += 1
        prev = b
    return out


def reduce(trace, recs):
    """(the readings the five metrics take, the fit); (None, None) where
    there is no fit."""
    f = fit(trace, recs)
    if f is None:
        return None, None
    lo, hi = trace.window
    gaps = trace_mod.gaps_of(trace.ops[0], lo, hi)
    dec = _seconds(recs, "serve.decode", f.base_ns) + f.device
    adm = _seconds(recs, "serve.admit", f.base_ns) + f.device
    idle = {name: 100.0 * float(overlap_s(gaps, trace_mod.merged(
        [trace_mod.Event(name, a, b - a) for a, b in iv], lo, hi))) / (hi - lo)
        for name, iv in (("decode", dec), ("admit", adm))}
    now = (dec[:, 0] >= lo) & (dec[:, 0] < hi)
    out = {"decode_host_idle_share": idle["decode"],
           "admit_host_idle_share": idle["admit"],
           "decode_tick_ms": None, "admit_stall_p95_ms": None}
    if now.any():
        out["decode_tick_ms"] = 1e3 * float(np.median(dec[now, 1]
                                                      - dec[now, 0]))
        out["admit_stall_p95_ms"] = 1e3 * harness.nearest_rank(
            stalls(dec, adm)[now].tolist(), 0.95)
    return out, f


def outside_ticks(trace, recs, shift, base_ns):
    """(share inside, largest overhang in seconds) of the window's decode
    and admission records, moved by `shift` seconds, against the
    `bench.tick` span holding each one's midpoint."""
    lo, hi = trace.window
    ticks = np.asarray(sorted((h.start, h.start + h.dur) for h in trace.host
                              if h.name == "bench.tick")).reshape(-1, 2)
    iv = np.concatenate([_seconds(recs, n, base_ns) for n in
                         ("serve.decode", "serve.admit")]) + shift
    iv = iv[(iv[:, 0] >= lo) & (iv[:, 1] <= hi)]
    if not len(iv) or not len(ticks):
        return None, None
    k = np.maximum(np.searchsorted(ticks[:, 0], iv.mean(1), "right") - 1, 0)
    over = np.maximum(np.maximum(ticks[k, 0] - iv[:, 0],
                                 iv[:, 1] - ticks[k, 1]), 0.0)
    return float((over == 0).mean()), float(over.max())


_LAST = {}


def reading(run, name):
    """One metric of `reduce` for this run (reduced once per run)."""
    if run.trace is None:
        return None
    if _LAST.get("run") is not run:
        recs = records()
        vals, f = reduce(run.trace, recs)
        _LAST.update(run=run, vals=vals)
        note(run.trace, recs, f)
    vals = _LAST["vals"]
    return None if vals is None else vals[name]


def note(trace, recs, f):
    """The fit, on standard error."""
    info = {"records": len(recs), "fit": f is not None}
    if f is not None:
        info.update(
            ticks=f.ticks, match_dev_us=round(f.match_dev_s * 1e6, 3),
            device_minus_host_ms=round((f.device - f.host) * 1e3, 4),
            bracket_ms=[round(x * 1e3, 4) for x in f.bracket])
        inside, over = outside_ticks(trace, recs, f.host, f.base_ns)
        info.update(inside_tick=inside, overhang_us=over and over * 1e6)
    print("bench spans: " + json.dumps(info), file=sys.stderr, flush=True)
