"""Launched tokens that were padding over launched tokens in the window,
from telemetry()["encoder"], %."""


def read(run):
    enc = run.stats.get("encoder")
    if not enc or not enc["launched_tokens"]:
        return None
    return 100.0 * (1.0 - enc["tokens"] / enc["launched_tokens"])
