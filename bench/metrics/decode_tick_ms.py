"""Median length of the engine's decode tick (`serve.decode`: inputs, the
decode program's launch, the next tokens' fetch, commit) over the traced
window, ms."""
from bench import spans


def read(run):
    return spans.reading(run, "decode_tick_ms")
