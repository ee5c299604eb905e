"""Reduction of a profiler trace (`.xplane.pb`) to what the per-layer
metrics read: device busy time, kernel and program times, and the idle
gaps with what the benchmark's host loop was doing in them.

What the trace holds today (read by hand on a TPU v5e, JAX 0.9):

- Device plane `/device:TPU:<i>`, line `XLA Ops`: one event per executed
  HLO instruction, named by the instruction's full text. Loop bodies'
  instructions nest inside their `while` event, so busy time is a union of
  intervals, never a sum.
- Line `XLA Modules`: one event per program execution, named
  `jit_<python function name>(<fingerprint>)`.
- The repository's Mosaic kernels carry no name of their own yet: each is
  a `custom_call_target="tpu_custom_call"` instruction named
  `%_dispatch.<n>`. They are told apart by their signature (`kernel_kind`).
- Host plane `/host:CPU`, line `python`: the benchmark's own
  `TraceAnnotation` spans (`bench.*`), on the same clock as the device.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def kernel_kind(op_text: str):
    """Which fused attention kernel an `XLA Ops` event is, from its HLO
    text, or None. The forward returns (o, lse) with lse's minor dim 1;
    dK/dV returns (dk, dv) of one shape; dQ returns a single array."""
    if TPU_CUSTOM_CALL not in op_text:
        return None
    result = op_text.split(" custom-call(", 1)[0].split(" = ", 1)[-1]
    if not result.startswith("("):
        return "fused_dq"
    if re.search(r",1\]\{", result):
        return "fused_fwd"
    return "fused_dkv"


def module_of(name: str) -> str:
    """`jit_prefill(1234)` -> `jit_prefill`."""
    return name.split("(", 1)[0]


def short_op(op_text: str) -> str:
    kind = kernel_kind(op_text)
    name = op_text.split(" ", 1)[0].lstrip("%")
    return f"{kind}:{name}" if kind else name


@dataclasses.dataclass
class Event:
    name: str
    start: float     # seconds on the trace's clock
    dur: float


@dataclasses.dataclass
class DeviceTrace:
    window: tuple              # (start, end) of the traced window
    ops: list                  # [Event] per device, XLA Ops
    modules: list              # [Event] per device, XLA Modules
    host: list                 # [Event] the benchmark's bench.* spans

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def busy_s(self):
        """Seconds in which some operation ran, averaged over devices."""
        per = [union_s(ev, *self.window) for ev in self.ops]
        return sum(per) / len(per) if per else 0.0

    def kernel_times(self):
        """Kernel kind -> (calls, seconds) summed over devices."""
        out = collections.defaultdict(lambda: [0, 0.0])
        for dev in self.ops:
            for e in clip(dev, *self.window):
                k = kernel_kind(e.name)
                if k:
                    out[k][0] += 1
                    out[k][1] += e.dur
        return {k: tuple(v) for k, v in out.items()}

    def module_times(self):
        """Program name -> (calls, seconds) summed over devices."""
        out = collections.defaultdict(lambda: [0, 0.0])
        for dev in self.modules:
            for e in clip(dev, *self.window):
                out[module_of(e.name)][0] += 1
                out[module_of(e.name)][1] += e.dur
        return {k: tuple(v) for k, v in out.items()}

    def kernel_times_in(self, module: str):
        """Kernel times restricted to executions of one program."""
        out = collections.defaultdict(lambda: [0, 0.0])
        for ops, mods in zip(self.ops, self.modules):
            spans = [(e.start, e.start + e.dur) for e in clip(mods, *self.window)
                     if module_of(e.name) == module]
            for e in clip(ops, *self.window):
                k = kernel_kind(e.name)
                if k and any(a <= e.start < b for a, b in spans):
                    out[k][0] += 1
                    out[k][1] += e.dur
        return {k: tuple(v) for k, v in out.items()}

    def top_ops(self, n=10):
        """The device operations that took most time (top level only:
        an instruction inside a loop counts, the loop itself does not)."""
        agg = collections.Counter()
        for dev in self.ops:
            for e in clip(dev, *self.window):
                if not e.name.split(" = ", 1)[0].startswith("%while"):
                    agg[short_op(e.name)] += e.dur
        return [[k, v] for k, v in agg.most_common(n)]

    def idle_gaps(self, n=10):
        """The longest stretches with no device operation, on device 0,
        each named by the innermost benchmark span covering its middle."""
        if not self.ops:
            return []
        gaps = gaps_of(self.ops[0], *self.window)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2
            inside = [h for h in self.host if h.start <= mid < h.start + h.dur]
            label = min(inside, key=lambda h: h.dur).name if inside else "none"
            out.append([label, b - a])
        return out


def clip(events, lo, hi):
    return [e for e in events if e.start < hi and e.start + e.dur > lo]


def merged(events, lo, hi):
    iv = sorted((max(e.start, lo), min(e.start + e.dur, hi))
                for e in clip(events, lo, hi))
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_s(events, lo, hi):
    return sum(b - a for a, b in merged(events, lo, hi))


def gaps_of(events, lo, hi):
    out, t = [], lo
    for a, b in merged(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def find_xplane(log_dir):
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path, window_span="bench.window") -> DeviceTrace:
    """Read a trace; the window is the (first) host span `window_span`, or,
    where the host spans are missing, from the first program execution's
    start to the last one's end."""
    ops, modules, host = planes(path)
    spans = [h for h in host if h.name == window_span]
    if spans:
        window = (spans[0].start, spans[0].start + spans[0].dur)
    else:
        runs = [e for dev in modules for e in dev]
        if not runs:
            raise ValueError(f"no {window_span!r} span and no program in "
                             f"{path}")
        window = (min(e.start for e in runs),
                  max(e.start + e.dur for e in runs))
    return DeviceTrace(window, ops, modules, host)


def planes(path):
    """(XLA Ops per device, XLA Modules per device, bench.* host spans)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append(_events(lines.get("XLA Ops")))
            modules.append(_events(lines.get("XLA Modules")))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [e for e in _events(ln) if e.name.startswith("bench.")]
    return ops, modules, host


def _events(line):
    if line is None:
        return []
    return [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]
