"""What the per-layer readers in bench/metrics/ share. Each reader is
`read(run) -> float | None`: None where the run gave it nothing to read,
so the harness leaves the metric out; never 0 for a share."""
from __future__ import annotations

import numpy as np

from bench import flops


def plan_nvalid(run, seq_len):
    """Per layer listed blocks per row block; a dense cell lists all."""
    if run.plan_nvalid is not None:
        return run.plan_nvalid
    nrb = seq_len // run.block
    return [np.full(nrb, nrb)] * run.model["num_layers"]


def idle_share(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)


def train_kernel_roofline(run, kind):
    """A training kernel's summed roofline time over its summed device time
    in the trace, %; each call counted as the mean layer's work."""
    t = run.trace
    if t is None or run.plan_nvalid is None:
        return None
    calls, seconds = t.kernel_times().get(kind, (0, 0.0))
    if not calls or seconds <= 0:
        return None
    m, rec = run.model, run.record
    B, L, blk = rec["batch"], rec["seq_len"], run.block
    dsize = flops.itemsize(m["dtype"])
    per_layer = []
    for nv in run.plan_nvalid:
        blocks = flops.valid_blocks(nv)
        kw = dict(batch=B, heads=m["num_heads"], hd=m["head_dim"], block=blk,
                  blocks=blocks, dsize=dsize)
        if kind == "fused_fwd":
            f, b = flops.fused_fwd(rows=L // blk, **kw)
        elif kind == "fused_dq":
            f, b = flops.fused_dq(rows=L // blk, **kw)
        else:
            f, b = flops.fused_dkv(kv_heads=m["num_kv_heads"], cols=L // blk,
                                   **kw)
        per_layer.append(flops.roofline_s(f, b, run.peaks))
    return 100.0 * calls * float(np.mean(per_layer)) / seconds


def traced_steps(run):
    """The serving steps that ran inside the traced part of the window."""
    until = run.record.get("trace_until")
    if until is None:
        return []
    return [s for s in run.record["steps"] if s["t1"] <= until]


def padded_len(run, prompt_len):
    """The prefill length the engine pads a prompt to."""
    blk = run.block
    b = -(-max(run.record["prefill_bucket"], blk) // blk) * blk
    cover = run.plan_nvalid[0].shape[0] * blk
    return min(max(-(-prompt_len // b) * b, b), cover)
