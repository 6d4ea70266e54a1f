"""Engine time per query: the benchmark's spans around each call into the
query engine (``execute`` and every ``read_next``)."""


def read(run):
    n = run.counters.get("queries")
    spent = run.counters.get("engine_s")
    return spent / n * 1e3 if n and spent is not None else None
