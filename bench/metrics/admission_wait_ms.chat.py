"""Due time to admission (``Request.arrival_s``, the start of the
serving turn that took the request in, on the batcher clock), p95 over
the requests due while the profiler ran."""
from bench import readers


def read(run):
    if run.trace is None:
        return None
    a, b = run.trace_span
    return readers.p95([w for d, w in run.stats.get("admission", [])
                        if a <= d <= b])
