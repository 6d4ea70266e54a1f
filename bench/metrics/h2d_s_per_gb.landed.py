"""Host-to-HBM seconds per GB landed: the benchmark's spans around each
``batch_to_device`` call (with the padding before it) plus each query's
final readiness wait."""


def read(run):
    landed = run.counters.get("bytes_landed")
    spent = run.counters.get("h2d_s", 0) + run.counters.get("ready_s", 0)
    return spent / (landed / 1e9) if landed and spent else None
