"""Device time of the encoder program (jit__encode_core) per route call
in the traced window, ms."""
PROGRAM = "_encode_core"


def read(run):
    if run.trace is None:
        return None
    seconds, n = run.trace.module_time(PROGRAM)
    calls = len(run.trace.spans.get("bench.route", []))
    return seconds / calls * 1e3 if n and calls else None
