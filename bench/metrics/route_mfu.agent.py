"""Work of every routed request over window x bf16 peak, %: its encoder
FLOPs (bench/encoder_work.py, real tokens) plus the flat scoring 2.N.D
that route_mfu.flood counts a decision."""
from bench import encoder_work, readers


def read(run):
    scoring = readers.route_mfu(run)
    if scoring is None:
        return None
    top = run.cfg["max_position_embeddings"]
    lengths = [min(it.prompt_bytes, top)
               for it in run.items[: run.stats["attempted"]]]
    encoder = encoder_work.least_s(run.cfg, lengths, run.peaks)
    return scoring + 100.0 * encoder / run.window_s
