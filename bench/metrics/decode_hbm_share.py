"""Bytes the decode ticks of the traced window had to read (the weights,
and K/V of the blocks each active slot's row lists) over the decode
program's device time times the HBM peak, %."""
from bench import readers

PROGRAM = "jit_serve_step"


def read(run):
    if run.trace is None:
        return None
    calls, seconds = run.trace.module_times().get(PROGRAM, (0, 0.0))
    ticks = [s["positions"] for s in readers.traced_steps(run)
             if s["positions"]]
    if not calls or seconds <= 0 or not ticks:
        return None
    w = run.family.weight_bytes(run.model)
    nbytes = sum(w + run.family.decode_cache_bytes(
        run.model, run.plan_nvalid, pos, run.block) for pos in ticks)
    return 100.0 * nbytes / (seconds * run.peaks["hbm_bytes_per_s"])
