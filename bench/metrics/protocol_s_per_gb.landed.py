"""The Thallus control plane per GB landed: self seconds of the program's
``thallus.scan``, ``init_scan``, ``iterate``, ``expose`` and ``finalize``
spans (each less the spans nested in it), over the query-result bytes
landed in the window (GB = 1e9 B)."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    landed = run.counters.get("bytes_landed")
    if not spans or not landed:
        return None
    return program_spans.protocol_self_s(spans) / (landed / 1e9)
