#!/usr/bin/env python3
"""Sweep of a serving cell's arrival rate, to find its knee once.

    python bench/sweep.py --workload <serving cell> --rates 4,6,8 --seconds 20

One set-up (weights, plan, engine, warm-up) serves the cell's mix at each
rate in turn, each for --seconds and then drained. Per rate it prints the
completed output tokens/s beside those offered, the p95 of time to first
token and of the inter-token gap, and the queue left when the window
closed. The knee is the highest rate whose queue does not grow over the
window; it is recorded as `knee_per_s` in the mixes (bench/traffic/),
whose rates are shares of it (PERF.md).
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench import gen, harness, peaks, serve, spec
    from bench.spec import program_config
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.setup_jax()
    cell = spec.cell(args.workload)
    cfg = program_config(cell.config)
    run = harness.Run(cell=cell,
                      peaks=peaks.peaks_for(jax.devices()[0].device_kind))
    eng, state, _ = serve.build(run, args.seed, cfg)
    serve.warm(eng, cell.traffic, cfg, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_per_s=rate)
        arrivals = gen.chat_arrivals(mix, args.seed, args.seconds,
                                     cfg.vocab_size)
        reqs, stamps, refused, steps, end = serve.window(
            run, eng, arrivals, args.seconds, mix, None)
        e2e = serve.measure(run, arrivals, reqs, stamps, refused,
                            args.seconds, end)
        queued = sum(1 for a in arrivals
                     if not stamps[a.rid] or stamps[a.rid][0] > args.seconds)
        print("sweep " + json.dumps(dict(
            rate=rate, requests=len(arrivals), failed=run.failed,
            queued_at_close=queued, drain_s=end - args.seconds,
            offered_tokens_per_s=sum(a.max_new for a in arrivals)
            / args.seconds,
            tokens_per_s=e2e["serve_output_tokens_per_s"],
            ttft_p95_ms=e2e["ttft_p95_ms"], itl_p95_ms=e2e["itl_p95_ms"])),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
