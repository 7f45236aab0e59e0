"""Flat scoring FLOPs 2.B.N.D of every decision over window x bf16 peak, %."""
from bench import readers


def read(run):
    return readers.route_mfu(run)
