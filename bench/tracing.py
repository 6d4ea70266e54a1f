"""From a profiler trace to the numbers the metric readers take.

The JAX profiler writes an ``.xplane.pb``; :func:`load_planes` reads it
with ``jax.profiler.ProfileData`` into plain tuples, and everything after
that is plain Python over ``(name, start_ns, duration_ns)`` events, so a
small recorded trace checks the arithmetic on the CPU.

* The window is the host span ``bench.window`` that the generator opens
  around its measured loop, so host and device events are read on the
  trace's own clock.
* A device is busy where any of its ``XLA Ops`` events runs; busy time is
  the union of those intervals inside the window.
* Idle gaps are the parts of the window where the device is not busy. Each
  piece is charged to the innermost ``bench.*`` host span covering it (what
  the benchmark's host thread was doing), else to ``host (no span)``.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "host (no span)"


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict          # line name -> list of (event name, start_ns, dur_ns)


def load_planes(xplane: Path) -> list[Plane]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        planes.append(Plane(plane.name, lines))
    return planes


def save_planes(planes: list[Plane], path: Path) -> None:
    """A trace as gzipped JSON, for the recorded trace the tests read."""
    with gzip.open(path, "wt") as f:
        json.dump([{"name": p.name, "lines": p.lines} for p in planes], f)


def read_planes(path: Path) -> list[Plane]:
    with gzip.open(path, "rt") as f:
        return [Plane(p["name"], {k: [tuple(e) for e in v]
                                  for k, v in p["lines"].items()})
                for p in json.load(f)]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def device_planes(planes: list[Plane]) -> list[Plane]:
    """The accelerator planes (``/device:TPU:<n>``), in device order."""
    devs = [p for p in planes if p.name.startswith("/device:")
            and OPS_LINE in p.lines]
    return sorted(devs, key=lambda p: int(p.name.rsplit(":", 1)[-1])
                  if p.name.rsplit(":", 1)[-1].isdigit() else 0)


def host_spans(planes: list[Plane]) -> list[tuple]:
    """Every ``bench.*`` span on any host thread."""
    out = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for events in p.lines.values():
            out.extend(e for e in events if e[0].startswith(SPAN_PREFIX))
    return out


def window_of(planes: list[Plane]) -> tuple[float, float]:
    spans = [e for e in host_spans(planes) if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    _, start, dur = spans[0]
    return start, start + dur


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def span_segments(spans: list[tuple]) -> list[tuple[float, float, str]]:
    """The host timeline cut where any span starts or ends, each piece
    labelled with the shortest span that covers it (the innermost)."""
    edges = []
    for i, (name, s, d) in enumerate(spans):
        if name != WINDOW_SPAN:
            edges += [(s, 1, i), (s + d, 0, i)]
    edges.sort()
    active: set[int] = set()
    out, t = [], None
    for x, opening, i in edges:
        if active and t is not None and x > t:
            inner = min(active, key=lambda j: spans[j][2])
            out.append((t, x, spans[inner][0]))
        (active.add if opening else active.discard)(i)
        t = x
    return out


def charge_gaps(idle: list[tuple[float, float]], spans: list[tuple]) -> dict:
    """Seconds of idle device time under each innermost host span; time
    under no span goes to ``NO_SPAN``. Both lists are walked once."""
    segs = span_segments(spans)
    charged: dict[str, float] = {}

    def charge(label, ns):
        if ns > 0:
            charged[label] = charged.get(label, 0.0) + ns * 1e-9

    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(segs) and segs[k][0] < b:
            s, e, label = segs[k]
            charge(NO_SPAN, min(s, b) - t)
            charge(label, min(e, b) - max(s, t))
            t = max(t, min(e, b))
            k += 1
        charge(NO_SPAN, b - t)
    return charged


def reduce(planes: list[Plane], chips: int) -> dict:
    """The trace's numbers over the window, averaged over the first
    ``chips`` devices: ``busy_s``, ``window_s``, per-op and per-module
    device seconds (and module counts), and idle seconds by host span."""
    lo, hi = window_of(planes)
    devs = device_planes(planes)[:chips]
    if len(devs) < chips:
        raise ValueError(f"trace has {len(devs)} device planes, the cell "
                         f"uses {chips}")
    spans = host_spans(planes)
    busy_s, ops, modules, idle = 0.0, {}, {}, {}
    for dev in devs:
        ops_events = dev.lines[OPS_LINE]
        busy = union(clip(ops_events, lo, hi))
        busy_s += sum(b - a for a, b in busy) * 1e-9
        for name, s, d in ops_events:
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                ops[name] = ops.get(name, 0.0) + part * 1e-9
        for name, s, d in dev.lines.get(MODULES_LINE, []):
            if s >= lo and s + d <= hi:
                n, t = modules.get(name, (0, 0.0))
                modules[name] = (n + 1, t + d * 1e-9)
        for label, sec in charge_gaps(gaps(busy, lo, hi), spans).items():
            idle[label] = idle.get(label, 0.0) + sec
    n = len(devs)
    return {"busy_s": busy_s / n, "window_s": (hi - lo) * 1e-9,
            "ops": {k: v / n for k, v in ops.items()},
            "modules": {k: (c / n, t / n) for k, (c, t) in modules.items()},
            "idle": {k: v / n for k, v in idle.items()}}


def module_time(summary: dict, prefix: str) -> tuple[float, float]:
    """(count, device seconds) of the modules whose name starts with
    ``prefix`` (a jitted function ``f`` runs as module ``jit_f``)."""
    count = total = 0.0
    for name, (c, t) in summary["modules"].items():
        if name.startswith(prefix):
            count += c
            total += t
    return count, total


def breakdown(summary: dict, top: int = 10) -> dict:
    def largest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": largest(summary["ops"]),
            "idle_gaps": largest(summary["idle"])}
