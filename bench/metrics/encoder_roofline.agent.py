"""Least time of the encoder work of the real tokens (bench/encoder_work.py,
FLOPs at the bf16 peak) over the encoder program's device time, per route
call in the traced window, %."""
from bench import encoder_work

PROGRAM = "_encode_core"


def read(run):
    st = run.stats
    if run.trace is None or not run.peaks or not st.get("calls"):
        return None
    seconds, n = run.trace.module_time(PROGRAM)
    calls = len(run.trace.spans.get("bench.route", []))
    if not n or not calls:
        return None
    top = run.cfg["max_position_embeddings"]
    lengths = [min(it.prompt_bytes, top)
               for it in run.items[: st["attempted"]]]
    per_call = encoder_work.least_s(run.cfg, lengths, run.peaks) \
        / st["calls"]
    return 100.0 * per_call * calls / seconds
