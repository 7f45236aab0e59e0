"""Route decisions returned in the window over the window, routes/s."""


def read(run):
    n = run.stats.get("batch_rows")
    return n / run.window_s if n else None
