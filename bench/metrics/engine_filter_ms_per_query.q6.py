"""The engine's filter per query: seconds in the program's
``engine.filter`` spans (``filter_mask`` over each scanned batch) over the
window's queries."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    n = run.counters.get("queries")
    found = spans.get(program_spans.names.ENGINE_FILTER) if spans else None
    return found["seconds"] / n * 1e3 if found and n else None
