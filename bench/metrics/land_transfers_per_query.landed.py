"""Host-to-HBM transfers per query: the ``transfers`` argument of the
program's ``thallus.land`` spans (1 for a batch landed from its receive
region, one per column otherwise), summed over the window's queries. A
program whose spans carry no ``transfers`` gives nothing."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    n = run.counters.get("queries")
    found = spans.get(program_spans.names.LAND) if spans else None
    transfers = found["args"].get("transfers") if found else None
    return transfers / n if transfers and n else None
