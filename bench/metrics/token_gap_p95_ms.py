"""Gap between successive tokens of a request, p95 over every gap that ends in the window."""
from bench import readers


def read(run):
    return readers.p95(run.stats.get("gap_ms", []))
