"""Measured transport host work per GB landed: the program's own timings
of buffer allocation, placement copy and batch assembly
(``TransportStats.alloc_s + wire.measured_copy_s + deserialize_s``).
Nothing modeled is read."""


def read(run):
    landed = run.counters.get("bytes_landed")
    spent = run.counters.get("transport_measured_s")
    return spent / (landed / 1e9) if landed and spent is not None else None
