"""The reduction of the program's own spans (bench/program_spans.py): on
hand-made events with known answers, and on a traced window of three Q6
queries recorded on a TPU v5e (``scan.lineitem-sf1.q6``, 0.3 s; device
ops and the benchmark's and the program's host spans only). The program
metrics' readers are checked on both."""
from __future__ import annotations

import pytest

from bench import harness, program_spans, tracing

RECORDED = harness.BENCH / "tests" / "data" / "q6_trace.json.gz"
PROGRAM_METRICS = ["engine_filter_ms_per_query.q6", "protocol_ms_per_query.q6",
                   "land_ms_per_query.q6", "land_puts_per_query.q6",
                   "protocol_s_per_gb.landed", "land_s_per_gb.landed"]
PROTOCOL = ("thallus.scan", "thallus.init_scan", "thallus.iterate",
            "thallus.expose", "thallus.finalize")


def span(name, start, dur, **args):
    return (name, start * 1e9, dur * 1e9, args)


def trace(threads, ops=()):
    return {"threads": threads,
            "devices": [tracing.Plane("/device:TPU:0",
                                      {tracing.OPS_LINE: list(ops)})]}


def metric(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def test_self_time_of_nested_spans_of_both_prefixes():
    main = [span("bench.window", 0, 100), span("bench.query", 10, 50),
            span("thallus.scan", 12, 46), span("thallus.iterate", 20, 30),
            span("engine.filter", 22, 8, rows=100),
            span("bench.h2d", 32, 8), span("thallus.land", 33, 6),
            span("thallus.expose", 42, 2)]
    other = [span("thallus.pull", 25, 20)]     # another thread: not nested
    r = program_spans.reduce(trace({"main": main, "other": other}), 1)
    want = {"thallus.scan": (46, 46 - 30),
            # a benchmark span nested in a program span is not its self
            # time either
            "thallus.iterate": (30, 30 - 8 - 8 - 2),
            "engine.filter": (8, 8), "thallus.land": (6, 6),
            "thallus.expose": (2, 2), "thallus.pull": (20, 20)}
    assert set(r["spans"]) == set(want)        # bench.* spans are not kept
    for name, (seconds, own) in want.items():
        assert r["spans"][name]["seconds"] == pytest.approx(seconds), name
        assert r["spans"][name]["self_seconds"] == pytest.approx(own), name
    assert program_spans.protocol_self_s(r["spans"]) == pytest.approx(
        16 + 12 + 2)


def test_args_are_summed_per_name():
    main = [span("bench.window", 0, 100),
            span("thallus.land", 10, 1, rows=4096, columns=2, bytes=32768),
            span("thallus.land", 20, 1, rows=1024, columns=3, bytes=12288),
            span("thallus.init_scan", 30, 1, start_batch=0, note="text")]
    r = program_spans.reduce(trace({"main": main}), 1)["spans"]
    assert r["thallus.land"]["count"] == 2
    assert r["thallus.land"]["args"] == {"rows": 5120, "columns": 5,
                                         "bytes": 45056}
    assert r["thallus.init_scan"]["args"] == {"start_batch": 0}


def test_spans_across_the_window_edges_are_clipped():
    main = [span("bench.window", 0, 100),
            span("thallus.scan", -10, 30), span("thallus.init_scan", -5, 10),
            span("thallus.scan", 90, 20), span("thallus.finalize", 95, 10),
            span("thallus.scan", 120, 10)]          # after the window
    r = program_spans.reduce(trace({"main": main}), 1)["spans"]
    scan = r["thallus.scan"]
    assert scan["seconds"] == pytest.approx(20 + 10)
    assert scan["self_seconds"] == pytest.approx(20 + 10 - 5 - 5)
    assert scan["count"] == 1                 # counted where it starts
    assert r["thallus.init_scan"]["seconds"] == pytest.approx(5)
    assert r["thallus.init_scan"]["count"] == 0
    assert r["thallus.finalize"]["seconds"] == pytest.approx(5)
    assert r["thallus.finalize"]["count"] == 1


def test_idle_gaps_split_further_by_the_program_spans():
    ops = [("x", 30e9, 10e9), ("y", 70e9, 5e9)]
    bench = [span("bench.window", 0, 100), span("bench.query", 10, 80),
             span("bench.engine", 15, 10), span("bench.h2d", 50, 20)]
    main = bench + [span("thallus.scan", 12, 76),
                    span("engine.filter", 16, 8),
                    span("thallus.iterate", 14, 70),
                    span("thallus.land", 52, 16)]
    # tracing.reduce reads every host event, the program's among them, as
    # load_planes leaves them, and charges idle time to bench.* alone
    planes = [tracing.Plane("/host:CPU", {"main": [e[:3] for e in main]}),
              tracing.Plane("/device:TPU:0", {tracing.OPS_LINE: ops})]
    idle = tracing.reduce(planes, 1)["idle"]
    assert idle == pytest.approx({tracing.NO_SPAN: 20, "bench.query": 35,
                                  "bench.engine": 10, "bench.h2d": 20})
    split = program_spans.reduce(trace({"main": main}, ops), 1)["idle"]
    assert sum(split.values()) == pytest.approx(sum(idle.values()))
    assert split == pytest.approx({
        tracing.NO_SPAN: 10 + 10, "bench.query": 2 + 2,
        "thallus.scan": 2 + 4, "thallus.iterate": 1 + 5 + 10 + 9,
        "bench.engine": 1 + 1, "engine.filter": 8,
        "bench.h2d": 2 + 2, "thallus.land": 16})
    # with no program span, the split is the benchmark's own
    alone = program_spans.reduce(trace({"main": bench}, ops), 1)["idle"]
    assert alone == pytest.approx(idle)


def test_metrics_read_nothing_without_program_spans():
    """A program from before its spans writes none, and an untraced run
    has no trace: each reader leaves its metric out."""
    counters = {"queries": 3, "bytes_landed": 1e9}
    untraced = harness.Run(counters=dict(counters))
    main = [span("bench.window", 0, 100), span("bench.query", 10, 50)]
    silent = harness.Run(counters=dict(counters), trace={
        "program": program_spans.reduce(trace({"main": main}), 1)})
    for name in PROGRAM_METRICS:
        assert metric(name).read(untraced) is None, name
        assert metric(name).read(silent) is None, name


@pytest.fixture(scope="module")
def recorded():
    return program_spans.read(RECORDED)


def test_recorded_q6_trace(recorded):
    r = program_spans.reduce(recorded, 1)
    spans = r["spans"]
    queries = spans["thallus.scan"]["count"]
    assert queries == 3
    for name in ("thallus.init_scan", "thallus.iterate", "thallus.finalize",
                 "engine.plan"):
        assert spans[name]["count"] == queries, name
    # 46 batches of 131,072 rows a query; Q6 keeps rows of every one
    batches = 46 * queries
    for name in ("engine.filter", "engine.take", "thallus.expose",
                 "thallus.pull", "thallus.sink", "thallus.land"):
        assert spans[name]["count"] == batches, name
    scanned = spans["engine.filter"]["args"]["rows"]
    assert scanned % queries == 0
    assert 45 * 131072 < scanned // queries <= 46 * 131072
    kept = spans["engine.take"]["args"]["rows"]
    assert 0 < kept < scanned / 20
    for name in ("thallus.expose", "thallus.pull", "thallus.sink"):
        assert spans[name]["args"]["rows"] == kept, name
    assert spans["thallus.pull"]["args"]["bytes"] == 8 * kept  # two int32
    assert spans["thallus.land"]["args"]["columns"] == 2 * batches
    assert spans["thallus.land"]["args"]["rows"] >= kept   # padded to 2^k
    for v in spans.values():
        assert 0 <= v["self_seconds"] <= v["seconds"] + 1e-9

    # the program's spans take over the idle time of the benchmark's
    planes = [tracing.Plane("/host:CPU", {k: [e[:3] for e in v] for k, v
                                          in recorded["threads"].items()}),
              *recorded["devices"]]
    idle = tracing.reduce(planes, 1)["idle"]
    split = r["idle"]
    assert sum(split.values()) == pytest.approx(sum(idle.values()),
                                                rel=1e-9)
    assert split.get("bench.query", 0) < 0.1 * idle["bench.query"]
    assert split.get("bench.engine", 0) < 0.1 * idle["bench.engine"]

    run = harness.Run(counters={"queries": queries,
                                "bytes_landed": 8 * kept},
                      trace={"program": r})
    got = {name: metric(name).read(run) for name in PROGRAM_METRICS}
    assert got["land_puts_per_query.q6"] == 92.0
    assert got["engine_filter_ms_per_query.q6"] == pytest.approx(
        spans["engine.filter"]["seconds"] / queries * 1e3)
    assert got["land_ms_per_query.q6"] == pytest.approx(
        spans["thallus.land"]["seconds"] / queries * 1e3)
    protocol_s = sum(spans[n]["self_seconds"] for n in PROTOCOL)
    assert got["protocol_ms_per_query.q6"] == pytest.approx(
        protocol_s / queries * 1e3)
    gb = 8 * kept / 1e9
    assert got["land_s_per_gb.landed"] == pytest.approx(
        spans["thallus.land"]["seconds"] / gb)
    assert got["protocol_s_per_gb.landed"] == pytest.approx(protocol_s / gb)
    # on the chip's host: the filter is most of the engine's 85 ms a
    # query, the control plane a few ms (loose bounds)
    assert 20 < got["engine_filter_ms_per_query.q6"] < 200
    assert 0 < got["protocol_ms_per_query.q6"] < 30


def test_a_run_reads_its_trace_once(tmp_path, monkeypatch):
    """``of_run`` reads the spans and their arguments from the profiler's
    ``.xplane.pb`` (recorded here on the CPU) and keeps the reduction with
    the run."""
    import jax
    from jax.profiler import TraceAnnotation

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with TraceAnnotation(tracing.WINDOW_SPAN):
            with TraceAnnotation("thallus.land", rows=1024, columns=2,
                                 bytes=8192):
                pass
            with TraceAnnotation("other.span", rows=5):
                pass
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(program_spans.chip, "TRACE_DIR", tmp_path)
    run = harness.Run(counters={"queries": 1}, trace={})
    spans = program_spans.of_run(run)
    assert set(spans) == {"thallus.land"}
    assert spans["thallus.land"]["count"] == 1
    assert spans["thallus.land"]["args"] == {"rows": 1024, "columns": 2,
                                             "bytes": 8192}
    assert run.trace["program"]["spans"] is spans
    assert metric("land_puts_per_query.q6").read(run) == 2.0
