"""Model FLOPs of the prompts prefilled in the traced window (the output
head once per prompt, attention over the listed blocks) over the device
time of the prefill program times the bf16 peak, %."""
from bench import flops, readers

PROGRAM = "jit_prefill"


def read(run):
    if run.trace is None:
        return None
    calls, seconds = run.trace.module_times().get(PROGRAM, (0, 0.0))
    lens = [p for s in readers.traced_steps(run) for p in s["admitted"]]
    if not calls or seconds <= 0 or not lens:
        return None
    work = sum(flops.prefill_flops(run.model, run.plan_nvalid, prompt_len=p,
                                   block=run.block) for p in lens)
    return 100.0 * work / (seconds * run.peaks["flops_bf16"])
