"""Training tokens in the steps completed in the window, over the window."""


def read(run):
    tokens = run.counters.get("tokens")
    return tokens / run.window_s if tokens else None
