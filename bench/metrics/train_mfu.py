"""Model FLOPs of the training step (6 per matmul parameter and token, 3x
the forward's 4 hd per listed score; the family's count) at the window's
step rate, over the cell's chips times one chip's bf16 peak, %. The rate
is taken with the profiler off."""
from bench import readers


def read(run):
    rec = run.record
    if not rec.get("steps") or rec.get("window_s", 0) <= 0:
        return None
    B, L = rec["batch"], rec["seq_len"]
    per_step = run.family.train_flops_per_step(
        run.model, readers.plan_nvalid(run, L), batch=B, seq_len=L,
        block=run.block)
    rate = rec["steps"] / rec["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["flops_bf16"])
