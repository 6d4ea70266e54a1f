"""Host-to-HBM time per query: spans around each ``batch_to_device``
call (with the padding before it) plus the query's final readiness wait."""


def read(run):
    n = run.counters.get("queries")
    spent = run.counters.get("h2d_s", 0) + run.counters.get("ready_s", 0)
    return spent / n * 1e3 if n and spent else None
