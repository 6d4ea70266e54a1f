"""Query-result bytes resident in HBM over the window (GB = 1e9 bytes)."""


def read(run):
    landed = run.counters.get("bytes_landed")
    return landed / 1e9 / run.window_s if landed else None
