"""Host-to-HBM landing per GB landed: seconds in the program's
``thallus.land`` spans (``batch_to_device``'s per-column puts) over the
query-result bytes landed in the window (GB = 1e9 B)."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    landed = run.counters.get("bytes_landed")
    found = spans.get(program_spans.names.LAND) if spans else None
    return found["seconds"] / (landed / 1e9) if found and landed else None
