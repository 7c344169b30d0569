"""Tokens trained in the window over the window's seconds; the window
ends when its last step is done."""


def read(run):
    n = run.stats.get("window_tokens")
    return n / run.window_s if n else None
