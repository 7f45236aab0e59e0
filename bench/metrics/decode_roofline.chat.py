"""Least time of the traced decode steps over their device time, %."""
from bench import readers


def read(run):
    return readers.decode_roofline(run)
