"""Share of the traced window with no operation on the device, %."""
from bench import readers


def read(run):
    return readers.idle_share(run)
