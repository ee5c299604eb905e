"""Share of the traced window with no operation on the device while the
host is inside the engine's decode tick (`serve.decode`), %."""
from bench import spans


def read(run):
    return spans.reading(run, "decode_host_idle_share")
