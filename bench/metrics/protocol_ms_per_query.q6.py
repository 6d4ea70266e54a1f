"""The Thallus control plane per query: self seconds of the program's
``thallus.scan``, ``init_scan``, ``iterate``, ``expose`` and ``finalize``
spans (each less the spans nested in it) over the window's queries."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    n = run.counters.get("queries")
    if not spans or not n:
        return None
    return program_spans.protocol_self_s(spans) / n * 1e3
