"""The trace reduction (bench/tracing.py): on hand-made events with known
answers, and on a small trace recorded on a TPU v5e (the feed cell's first
two train steps in a traced window, device ops and the benchmark's host
spans only)."""
from __future__ import annotations

import pytest

from bench import harness, tracing

RECORDED = harness.BENCH / "tests" / "data" / "feed_trace.json.gz"


def planes(ops_by_device, spans, modules=()):
    out = [tracing.Plane("/host:CPU", {"python3": list(spans)})]
    for i, ops in enumerate(ops_by_device):
        out.append(tracing.Plane(f"/device:TPU:{i}", {
            tracing.OPS_LINE: list(ops), tracing.MODULES_LINE: list(modules)}))
    return out


WINDOW = ("bench.window", 0.0, 100e9)


def test_busy_idle_and_spans():
    ops = [("a", 10e9, 10e9), ("b", 15e9, 15e9), ("a", 50e9, 10e9),
           ("c", 95e9, 10e9)]                     # the last one runs past
    spans = [WINDOW, ("bench.step", 0.0, 12e9), ("bench.sync", 30e9, 40e9),
             ("bench.loader", 35e9, 5e9)]
    r = tracing.reduce(planes([ops], spans), 1)
    assert r["window_s"] == pytest.approx(100.0)
    assert r["busy_s"] == pytest.approx(20 + 10 + 5)
    assert r["ops"] == pytest.approx({"a": 20.0, "b": 15.0, "c": 5.0})
    # idle: [0,10) under step, [30,50) under sync but [35,40) under the
    # inner loader span, [60,95) partly under sync (to 70), the rest none
    assert r["idle"] == pytest.approx({"bench.step": 10.0, "bench.sync": 25.0,
                                       "bench.loader": 5.0,
                                       tracing.NO_SPAN: 25.0})
    assert sum(r["idle"].values()) == pytest.approx(100 - r["busy_s"])


def test_devices_are_averaged_and_modules_counted():
    mods = [("jit_train_step(1)", 10e9, 20e9), ("jit_train_step(1)", 60e9,
                                                 20e9),
            ("jit_other", 90e9, 20e9)]           # past the window's end
    r = tracing.reduce(planes([[("x", 0.0, 50e9)], [("x", 0.0, 10e9)]],
                              [WINDOW], mods), 2)
    assert r["busy_s"] == pytest.approx(30.0)
    assert tracing.module_time(r, "jit_train_step") == pytest.approx(
        (2.0, 40.0))
    assert tracing.module_time(r, "jit_nothing") == (0, 0)


def test_window_must_be_one_span():
    with pytest.raises(ValueError):
        tracing.reduce(planes([[]], []), 1)
    with pytest.raises(ValueError):
        tracing.reduce(planes([[]], [WINDOW, WINDOW]), 1)
    with pytest.raises(ValueError):          # fewer device planes than chips
        tracing.reduce(planes([[]], [WINDOW]), 2)


def test_breakdown_is_the_largest_ten():
    summary = {"ops": {f"op{i}": float(i) for i in range(20)},
               "idle": {"bench.sync": 2.0, tracing.NO_SPAN: 1.0}}
    b = tracing.breakdown(summary)
    assert [name for name, _ in b["device_ops"]] == [
        f"op{i}" for i in range(19, 9, -1)]
    assert b["idle_gaps"] == [["bench.sync", 2.0], [tracing.NO_SPAN, 1.0]]


@pytest.fixture(scope="module")
def recorded():
    return tracing.read_planes(RECORDED)


def test_recorded_trace(recorded):
    r = tracing.reduce(recorded, 1)
    lo, hi = tracing.window_of(recorded)
    ops = tracing.device_planes(recorded)[0].lines[tracing.OPS_LINE]
    # busy time by brute force: sort the clipped intervals and sweep
    busy, end = 0.0, lo
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops
                       if s < hi and s + d > lo):
        if e > end:
            busy += e - max(s, end)
            end = e
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    # two whole train steps of about half a second each
    count, seconds = tracing.module_time(r, "jit_train_step")
    assert count == 2
    assert 0.4 < seconds / count < 0.7
    assert set(r["idle"]) <= {"bench.loader", "bench.device_put",
                              "bench.step", "bench.sync", tracing.NO_SPAN}
