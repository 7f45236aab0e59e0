"""Model FLOPs processed in the window over window x bf16 peak, %."""
from bench import readers


def read(run):
    return readers.serve_mfu(run)
