"""Tokens of every step completed in the window, over all chips, per
second of the window (host clock)."""


def read(run):
    return run.tokens / run.window.window_s if run.window.steps else None
