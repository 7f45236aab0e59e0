"""Device time per prefill program execution (a chunk step, or a
single-shot prefill) in the trace."""
from bench import readers


def read(run):
    return readers.mean_device_ms(run, *readers.PREFILL)
