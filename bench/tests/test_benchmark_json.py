"""BENCHMARK.json against the rules it is written to, and every name in
it found as a file of the benchmark."""
from __future__ import annotations

import re

import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (harness.ROOT / p).is_dir()
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(one_line(w) for w in SPEC["command"])
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(harness.ROOT.joinpath("BENCHMARK.json").read_bytes()) \
        <= 64 * 1024


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    assert 1 <= len(SPEC["configs"]) <= 24
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert c["file"] not in files
        files.add(c["file"])
        body = harness.load_json(harness.ROOT / c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        # every key cut from the source is named, with its reason, in the
        # file; no width is ever cut
        assert set(c["reduced"]) == set(body["reduced"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")), key
        assert (harness.BENCH / "kinds" / f"{body['kind']}.py").is_file()
        assert (harness.BENCH / "reference" / f"{body['kind']}.py").is_file()


def test_workloads():
    configs = {c["name"] for c in SPEC["configs"]}
    pairs = set()
    four = 0
    assert 1 <= len(SPEC["workloads"]) <= 24
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = harness.load_json(
            harness.BENCH / "traffic" / f"{w['traffic']}.json")
        generator = traffic["generator"]
        assert (harness.BENCH / "generators" / f"{generator}.py").is_file()
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_metrics(group):
    cells = {w["name"] for w in SPEC["workloads"]}
    keys = {"end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[group]
    for m in SPEC[group]:
        assert set(m) - {"workloads"} == keys, m["name"]
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        if group == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert one_line(m["layer"])
            if m["unit"] == "%" and "roofline" in m["name"]:
                assert m["name"].endswith("_roofline")


def test_every_cell_reports_what_it_must():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in e2e["setup_s"]
    for w in SPEC["workloads"]:
        cell = harness.find_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    folded = {" ".join(layer.lower().split()) for layer in layers}
    assert len(folded) == len(layers)
