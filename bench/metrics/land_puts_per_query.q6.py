"""Host-to-HBM transfers per query: the ``columns`` argument of the
program's ``thallus.land`` spans (one ``device_put`` per column of each
landed batch), summed over the window's queries."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    n = run.counters.get("queries")
    found = spans.get(program_spans.names.LAND) if spans else None
    puts = found["args"].get("columns") if found else None
    return puts / n if puts and n else None
