"""Queries completed in the window, over the window."""


def read(run):
    n = run.counters.get("queries")
    return n / run.window_s if n else None
