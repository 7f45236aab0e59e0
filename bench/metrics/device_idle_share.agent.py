"""1 - device busy union over the traced window, %."""
from bench import readers


def read(run):
    return readers.idle_share(run)
