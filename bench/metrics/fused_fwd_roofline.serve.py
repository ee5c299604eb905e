"""The fused forward kernel's roofline share inside the prefill program,
%: each prompt's padded length, every layer's listed blocks."""
from bench import flops, readers

PROGRAM = "jit_prefill"


def read(run):
    if run.trace is None:
        return None
    calls, seconds = run.trace.kernel_times_in(PROGRAM).get("fused_fwd",
                                                           (0, 0.0))
    lens = [p for s in readers.traced_steps(run) for p in s["admitted"]]
    if not calls or seconds <= 0 or not lens:
        return None
    m, blk = run.model, run.block
    dsize = flops.itemsize(m["dtype"])
    t_min = 0.0
    for p in lens:
        rows = readers.padded_len(run, p) // blk
        for nv in run.plan_nvalid:
            f, b = flops.fused_fwd(batch=1, heads=m["num_heads"],
                                   hd=m["head_dim"], block=blk, rows=rows,
                                   blocks=flops.valid_blocks(nv, rows),
                                   dsize=dsize)
            t_min += flops.roofline_s(f, b, run.peaks)
    return 100.0 * t_min / seconds
