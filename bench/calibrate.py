#!/usr/bin/env python3
"""Readings for a cell's correctness limits, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--out readings.jsonl]

For each seed it runs the cell as bench/run.py does (a training cell's
window can be short: the check reads the first steps), then reads besides
the program's numbers those of the controls, the reference computed in
float8 and in int8 (the family's reference, bench/families/) in the
program's place, and of the planted faults the cell can have: half of
each batch left out (training), a served token altered (serving). A
state left unchanged reads 1 by the training check's measure and needs
no run. Each control and fault is judged against the cell's limits
(bench/limits/<cell>.json) as a run is (`harness.passes`). One JSON line
per seed, on standard output and appended to --out; a training line also
holds every leaf's norms, so that another number can be worked out from
it later.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def judged(readings, limits):
    """name of each control or fault -> whether a run reading as it does
    would count as correct under `limits`."""
    from bench import harness
    names = sorted({k.split(".")[0] for k in readings if "." in k})
    return {n: harness.passes([(m, readings[f"{n}.{m}"], lim)
                               for m, lim in limits.items()
                               if f"{n}.{m}" in readings])
            for n in names}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax
    from bench import harness, peaks, spec
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    counter = harness.setup_jax()
    cell = spec.cell(args.workload)
    driver = spec.driver(cell.traffic["kind"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = harness.Run(cell=cell,
                          peaks=peaks.peaks_for(jax.devices()[0].device_kind))
        driver.run(run, seed=seed, seconds=args.seconds, trace=False,
                   t_process=t0, compiles=counter, control=True)
        limits = cell.limits["limits"]
        line = {"workload": cell.name, "seed": seed,
                "readings": run.record["readings"],
                "correct": run.correct,
                "judged_at_limits": judged(run.record["readings"], limits),
                "limits": limits,
                "e2e": run.e2e, "failed": run.failed,
                "attempted": run.attempted,
                "memory_peak_bytes": run.memory_peak_bytes,
                "seconds": time.perf_counter() - t0}
        print("calibrate " + json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(dict(
                    line, norms=run.record.get("norms"))) + "\n")
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
