"""The program's host spans on the scan path (``repro.obs.spans``), read
back from a profiler trace recorded on the CPU: a filtered and a
projection query through ``ThallusClient``, each batch landed through
``batch_to_device``. Every span of the table appears, nested as the
module documents, and its counts add up to the rows of the table and of
the answer."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import ThallusClient, ThallusServer
from repro.core.device_transport import batch_to_device
from repro.engine import Engine, make_numeric_table
from repro.obs import spans

ROWS, BATCH_ROWS = 10_000, 2_048
BATCHES = -(-ROWS // BATCH_ROWS)
FILTERED = ("SELECT c0, c2 FROM t WHERE c1 > 0.5", ["c0", "c2"])
PROJECTION = ("SELECT c0, c1, c3 FROM t", ["c0", "c1", "c3"])

# each span's innermost enclosing program span
PARENT = {spans.SCAN: None, spans.INIT_SCAN: spans.SCAN,
          spans.ENGINE_PLAN: spans.INIT_SCAN, spans.ITERATE: spans.SCAN,
          spans.ENGINE_FILTER: spans.ITERATE,
          spans.ENGINE_TAKE: spans.ITERATE, spans.EXPOSE: spans.ITERATE,
          spans.PULL: spans.ITERATE, spans.SINK: spans.ITERATE,
          spans.LAND: spans.SINK, spans.FINALIZE: spans.SCAN}


def read_spans(trace_dir) -> list[tuple]:
    """The program's spans in the trace: (thread, name, start_ns, end_ns,
    args), host planes only."""
    from jax.profiler import ProfileData

    (xplane,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(line.name, e.name, e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(spans.PREFIXES)]
    return out


def parent_of(span, found) -> str | None:
    """The shortest other span on the same thread that holds ``span``."""
    thread, _, s, e, _ = span
    holders = [o for o in found if o is not span and o[0] == thread
               and o[2] <= s and e <= o[3]]
    return min(holders, key=lambda o: o[3] - o[2])[1] if holders else None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Both queries run under the profiler; per query, the client, the
    landed batches and the spans inside its ``thallus.scan``."""
    import jax

    table = make_numeric_table("t", ROWS, 4, batch_rows=BATCH_ROWS, seed=3,
                               dtype="float32")
    engine = Engine()
    engine.register("t", table)
    server = ThallusServer(engine)
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    runs = []
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        for sql, _ in (FILTERED, PROJECTION):
            landed = []
            client = ThallusClient(server, sink=lambda b, landed=landed:
                                   landed.append(batch_to_device(b)))
            client.run_query(sql, "t")
            jax.block_until_ready([d.columns for d in landed])
            runs.append((client, landed))
    finally:
        jax.profiler.stop_trace()
    found = read_spans(trace_dir)
    scans = sorted((s for s in found if s[1] == spans.SCAN),
                   key=lambda s: s[2])
    assert len(scans) == 2
    per_query = [[s for s in found if q[2] <= s[2] and s[3] <= q[3]]
                 for q in scans]
    columns = {n: np.concatenate([b.column(n).values for b in table.batches])
               for n in ("c0", "c1", "c2", "c3")}
    return columns, list(zip(runs, per_query)), found


def total(found, name, arg):
    return sum(s[4][arg] for s in found if s[1] == name)


def count(found, name):
    return sum(s[1] == name for s in found)


def test_every_span_appears_nested_as_documented(traced):
    _, queries, found = traced
    for which, ((client, _), mine) in enumerate(queries):
        # the projection has no WHERE, so nothing to filter
        want = set(PARENT) - ({spans.ENGINE_FILTER} if which else set())
        assert {s[1] for s in mine} == want
        for span in mine:
            assert parent_of(span, found) == PARENT[span[1]], span[1]
        for name in (spans.SCAN, spans.INIT_SCAN, spans.ITERATE,
                     spans.FINALIZE, spans.ENGINE_PLAN):
            assert count(mine, name) == 1, name
        shipped = len(client.batches)
        for name in (spans.ENGINE_TAKE, spans.EXPOSE, spans.PULL,
                     spans.SINK, spans.LAND):
            assert count(mine, name) == shipped, name
        assert total(mine, spans.INIT_SCAN, "start_batch") == 0


@pytest.mark.parametrize("which", [0, 1], ids=["filtered", "projection"])
def test_rows_add_up(traced, which):
    columns, queries, _ = traced
    (client, landed), mine = queries[which]
    want_rows = int(np.sum(columns["c1"] > 0.5)) if which == 0 else ROWS
    answer_rows = sum(b.num_rows for b in client.batches)
    assert answer_rows == want_rows
    if which == 0:
        assert total(mine, spans.ENGINE_FILTER, "rows") == ROWS
        assert count(mine, spans.ENGINE_FILTER) == BATCHES
    for name in (spans.ENGINE_TAKE, spans.EXPOSE, spans.PULL, spans.SINK,
                 spans.LAND):
        assert total(mine, name, "rows") == answer_rows, name
    assert sum(d.num_rows for d in landed) == answer_rows


@pytest.mark.parametrize("which", [0, 1], ids=["filtered", "projection"])
def test_transfers_and_bytes_add_up(traced, which):
    _, queries, _ = traced
    (client, landed), mine = queries[which]
    selected = (FILTERED, PROJECTION)[which][1]
    batches = client.batches
    assert total(mine, spans.LAND, "columns") == len(batches) * len(selected)
    assert total(mine, spans.LAND, "bytes") == sum(
        c.values.nbytes for b in batches for c in b.columns)
    assert total(mine, spans.PULL, "bytes") == sum(b.nbytes for b in batches)
    assert total(mine, spans.PULL, "segments") == total(
        mine, spans.EXPOSE, "segments") == 3 * len(batches) * len(selected)
