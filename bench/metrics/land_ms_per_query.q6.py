"""Host-to-HBM landing per query: seconds in the program's
``thallus.land`` spans (``batch_to_device``'s per-column puts) over the
window's queries."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    n = run.counters.get("queries")
    found = spans.get(program_spans.names.LAND) if spans else None
    return found["seconds"] / n * 1e3 if found and n else None
