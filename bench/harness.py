"""What every cell's run shares: the device and its peaks, the compile
cache, the count of compiles inside the window, the profiler trace of part
of the window, the plan's summary line, and the result line."""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys

import jax
import numpy as np

from bench import peaks as peak_table
from bench import spec, trace as trace_mod

TRACE_DIR = os.path.join(spec.ROOT, ".bench_traces")


@dataclasses.dataclass
class Run:
    """One run's record: what the window measured, what the check compared,
    and what the per-layer readers read (`bench/metrics/*.py` get this)."""
    cell: spec.Cell
    peaks: dict
    e2e: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)   # (name, value, limit)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    plan_nvalid: list = None        # per layer (nrb,) listed blocks; None: dense
    block: int = 0
    record: dict = dataclasses.field(default_factory=dict)  # driver's own
    trace: trace_mod.DeviceTrace = None

    @property
    def model(self):
        return self.cell.model

    @property
    def correct(self):
        return passes(self.checks)


def passes(checks):
    """Whether every (name, value, limit) compared is within its limit."""
    return bool(checks) and all(math.isfinite(v) and v <= lim
                                for _, v, lim in checks)


class CompileCounter:
    """Counts program compilations, and loads from the persistent cache
    (which also mean a program was missing), while `armed`."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if self.armed and event == self.COMPILE:
            self.count += 1

    def _on_event(self, event, **_):
        if self.armed and event == self.CACHE_HIT:
            self.count += 1


def setup_jax():
    """The persistent compile cache in the checkout, every program in it."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CompileCounter()


class Tracer:
    """A profiler trace of part of the window, in the checkout."""

    def __init__(self, tag):
        self.dir = os.path.join(TRACE_DIR, tag)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        self.span = None

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()

    def stop(self):
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self):
        try:
            return trace_mod.load(trace_mod.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name, on):
    """A host span in the trace, or nothing."""
    import contextlib
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def plan_line(cell, tables, block, density):
    """The plan's per-layer density and digest, printed before the window."""
    from repro.core.spion import plan_digest
    if tables is None:
        info = {"plan": "dense"}
    else:
        nv = np.asarray(tables["nvalid"])
        nrb = nv.shape[1]
        arrays = {k: np.asarray(v) for k, v in tables.items()
                  if hasattr(v, "shape")}
        info = {"plan_digest": plan_digest(arrays, block),
                "density": density,
                "layer_density": [round(float(x) / (nrb * nrb), 6)
                                  for x in nv.sum(axis=1)],
                "block": int(block), "row_blocks": int(nrb)}
    print("bench plan: " + json.dumps(dict(info, workload=cell.name)),
          flush=True)


def note(what, t_process):
    """A progress line on standard error: what the run has done, and when."""
    import time
    print(f"bench: {what} at {time.perf_counter() - t_process:.1f} s",
          file=sys.stderr, flush=True)


def memory_peak():
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def nearest_rank(values, q):
    """The q-quantile by nearest rank (no interpolation)."""
    v = sorted(values)
    return v[max(int(math.ceil(q * len(v))) - 1, 0)]


def block_masks(tables):
    """Per layer (nrb, ncb) bool: the blocks the plan lists."""
    col = np.asarray(tables["col_idx"])
    nv = np.asarray(tables["nvalid"])
    L, nrb, K = col.shape
    out = np.zeros((L, nrb, nrb), bool)
    for layer in range(L):
        for r in range(nrb):
            c = col[layer, r, :nv[layer, r]]
            out[layer, r, c[c >= 0]] = True
    return out


def run_cell(cell, *, seed, seconds, trace, t_process):
    counter = setup_jax()
    dev = jax.devices()[0]
    peaks = peak_table.peaks_for(dev.device_kind)
    kind = cell.traffic["kind"]
    if kind == "train":
        from bench import train as driver
    elif kind == "serve_open_loop":
        from bench import serve as driver
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    run = Run(cell=cell, peaks=peaks)
    driver.run(run, seed=seed, seconds=seconds, trace=trace,
               t_process=t_process, compiles=counter)
    return result(run, trace)


def result(run, trace):
    metrics = {}
    if not trace:
        for m in run.cell.end_to_end:
            metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                  "unit": m["unit"]}
    else:
        for m in run.cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.idle_gaps(10)}
    out["checks"] = {n: {"value": finite(v), "limit": lim}
                     for n, v, lim in run.checks}
    return out


def finite(v):
    """A number for the result line; None where there is none."""
    v = float(v)
    return v if math.isfinite(v) else None


def print_checks(result):
    for n, c in result["checks"].items():
        print(f"check {n}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
