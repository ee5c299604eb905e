"""Nearest-rank p95, over the traced window's decode ticks, of the
admission time (`serve.admit`) since the previous tick: the stall every
active slot's next token waits out, ms."""
from bench import spans


def read(run):
    return spans.reading(run, "admit_stall_p95_ms")
