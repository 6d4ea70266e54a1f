"""Measured transport host work per query (``TransportStats.alloc_s +
wire.measured_copy_s + deserialize_s``); nothing modeled is read."""


def read(run):
    n = run.counters.get("queries")
    spent = run.counters.get("transport_measured_s")
    return spent / n * 1e3 if n and spent is not None else None
