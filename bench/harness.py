"""What every cell shares: finding a cell's files by name, the run record
the metric readers read, the checks that decide ``correct``, and the
result line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the deployment as it is run; its
  ``kind`` names the code that builds it, ``bench/kinds/<kind>.py``, and
  the plain reference ``bench/reference/<kind>.py``;
* ``bench/traffic/<traffic>.json``: the mix; its ``generator`` names the
  general generator ``bench/generators/<generator>.py`` that reads it;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from types import ModuleType
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A cell that cannot run as asked: no accelerator, too few chips, a
    device without peaks, a file that is not there."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} is not there")
    return json.loads(path.read_text())


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots and
    dashes, which an import statement cannot)."""
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} is not there")
    rel = path.resolve().relative_to(BENCH).with_suffix("")
    name = "bench_file." + "/".join(rel.parts).replace(".", "_") \
        .replace("-", "_").replace("/", ".")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str) -> Cell:
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(there are {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def kind_module(config: dict) -> ModuleType:
    return load_module(BENCH / "kinds" / f"{config['kind']}.py")


def reference_module(config: dict) -> ModuleType:
    return load_module(BENCH / "reference" / f"{config['kind']}.py")


def generator_module(traffic: dict) -> ModuleType:
    return load_module(BENCH / "generators" / f"{traffic['generator']}.py")


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a device that is not
    in ``bench/peaks.json`` is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (it has {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# what a run hands the metric readers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """One run's raw readings. Counters and spans are summed over the
    window only; ``trace`` is the reduced profiler trace of a ``--trace 1``
    run, else ``None``."""

    setup_s: float = 0.0
    window_s: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)
    latencies_s: list = dataclasses.field(default_factory=list)
    trace: dict | None = None
    config: dict = dataclasses.field(default_factory=dict)
    traffic: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    chips: int = 1

    def add(self, name: str, value: float) -> None:
        self.counters[name] = self.counters.get(name, 0) + value


def read_metrics(entries: list, run: Run, required: bool) -> dict:
    """Each entry's reader over ``run``. A reader that returns ``None`` is
    left out of the line; where ``required`` (the end-to-end metrics) that
    is an error."""
    out = {}
    for entry in entries:
        reader = load_module(BENCH / "metrics" / f"{entry['name']}.py")
        value = reader.read(run)
        if value is None:
            if required:
                raise BenchError(f"metric {entry['name']} found nothing to "
                                 "read in this run")
            continue
        if not math.isfinite(value):
            raise BenchError(f"metric {entry['name']} read {value}")
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


class Checks:
    """The numbers compared to decide ``correct``, each with its limit.
    A number passes when it is at most its limit."""

    def __init__(self):
        self.items: dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.items.values())

    def lines(self) -> list[str]:
        return [f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
                f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}"
                for name, c in self.items.items()]


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: Checks,
                breakdown: dict | None = None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks.items           # read last, after the metrics
    return json.dumps(line)


def emit(line: str, checks: Checks) -> None:
    """The result line last on stdout, the checks last on stderr."""
    print(line, flush=True)
    for text in checks.lines():
        print(text, file=sys.stderr)
    sys.stderr.flush()
