"""The readers of ``land_transfers_per_query.landed`` and ``.q6``: the
``transfers`` argument of the program's ``thallus.land`` spans per query,
on a hand-made reduction, on a window recorded on the CPU through the
program's own landing, and silent where the spans carry no ``transfers``
(a program from before it, as in the recorded Q6 trace) or are absent."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness, program_spans, tracing

READERS = ["land_transfers_per_query.landed", "land_transfers_per_query.q6"]
RECORDED = harness.BENCH / "tests" / "data" / "q6_trace.json.gz"


def metric(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def span(name, start, dur, **args):
    return (name, start * 1e9, dur * 1e9, args)


def reduced(events):
    return program_spans.reduce(
        {"threads": {"main": [span("bench.window", 0, 100), *events]},
         "devices": [tracing.Plane("/device:TPU:0", {tracing.OPS_LINE: []})]},
        1)


@pytest.mark.parametrize("name", READERS)
def test_transfers_per_query(name):
    r = reduced([span("thallus.land", 10, 1, rows=131072, columns=4,
                      transfers=1, bytes=1 << 21),
                 span("thallus.land", 20, 1, rows=131072, columns=4,
                      transfers=1, bytes=1 << 21),
                 span("thallus.land", 30, 1, rows=4096, columns=4,
                      transfers=4, bytes=1 << 16)])
    run = harness.Run(counters={"queries": 2}, trace={"program": r})
    assert metric(name).read(run) == pytest.approx(3.0)


@pytest.mark.parametrize("name", READERS)
def test_silent_without_transfers(name):
    no_arg = reduced([span("thallus.land", 10, 1, rows=4096, columns=2,
                           bytes=32768)])
    no_span = reduced([span("thallus.pull", 10, 1, rows=4096)])
    for r in (no_arg, no_span, {"spans": {}, "idle": {}}):
        run = harness.Run(counters={"queries": 1}, trace={"program": r})
        assert metric(name).read(run) is None
    assert metric(name).read(harness.Run(counters={"queries": 1})) is None


@pytest.mark.parametrize("name", READERS)
def test_silent_on_the_recorded_q6_trace(name):
    """Recorded before the spans carried ``transfers``."""
    r = program_spans.reduce(program_spans.read(RECORDED), 1)
    assert r["spans"]["thallus.land"]["args"]["columns"] > 0
    run = harness.Run(counters={"queries": 3}, trace={"program": r})
    assert metric(name).read(run) is None


def test_a_cpu_window_of_the_programs_landing(tmp_path, monkeypatch):
    """Three pulls of one layout and a fresh batch, landed through
    ``batch_to_device`` under the profiler on the CPU: 3 + 1 + 1 + 3
    transfers in two queries."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import Fabric, batch_from_arrays, expose_batch, schema
    from repro.core import device_transport
    from repro.core.transport import rdma_pull_batch

    monkeypatch.setattr(device_transport, "_SEEN",
                        device_transport._SeenLayouts(64))
    sch = schema(("a", "int32"), ("b", "float32"), ("c", "uint8"))
    rng = np.random.default_rng(7)
    fresh = batch_from_arrays(sch, [rng.integers(0, 9, 3001, dtype=np.int32),
                                    rng.standard_normal(3001, np.float32),
                                    rng.integers(0, 9, 3001, dtype=np.uint8)])
    pulled = [rdma_pull_batch(Fabric(), sch, fresh.num_rows,
                              expose_batch(fresh))[0] for _ in range(3)]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with TraceAnnotation(tracing.WINDOW_SPAN):
            landed = [device_transport.batch_to_device(b)
                      for b in (*pulled, fresh)]
            jax.block_until_ready([d.columns for d in landed])
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(program_spans.chip, "TRACE_DIR", tmp_path)
    run = harness.Run(counters={"queries": 2}, trace={})
    land = program_spans.of_run(run)["thallus.land"]
    assert land["count"] == 4
    assert land["args"]["columns"] == 12 and land["args"]["transfers"] == 8
    for name in READERS:
        assert metric(name).read(run) == pytest.approx(4.0)
