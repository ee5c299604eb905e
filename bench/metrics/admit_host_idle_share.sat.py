"""Share of the traced window with no operation on the device while the
host is admitting a request (`serve.admit`), in the saturated cell, %."""
from bench import spans


def read(run):
    return spans.reading(run, "admit_host_idle_share")
