"""Device time per pooled decode step program in the trace."""
from bench import readers


def read(run):
    return readers.mean_device_ms(run, readers.DECODE)
