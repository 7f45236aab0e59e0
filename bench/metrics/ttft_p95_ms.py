"""Due time to first token, p95 over every request due in the window."""
from bench import readers


def read(run):
    return readers.p95(run.stats.get("ttft_ms", []))
