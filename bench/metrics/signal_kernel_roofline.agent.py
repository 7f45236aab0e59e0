"""Least time of the configured signal work over the signal kernels' device time, %."""
from bench import readers


def read(run):
    return readers.signal_kernel_roofline(run)
