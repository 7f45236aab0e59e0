"""Seconds from process start to the window: load, build, warm, compile."""


def read(run):
    return run.setup_s
