"""The program's own host spans in a traced window, reduced per span name.

The program records ``jax.profiler.TraceAnnotation`` spans on its scan
path (``thallus.*``, ``engine.*``; the names and what each counts are in
``repro/obs/spans.py``), with counts as keyword arguments that arrive as
the event's stats. ``bench/tracing.py`` reads events without their stats
and charges idle time to the benchmark's spans alone, so this module reads
the run's ``.xplane.pb`` again, once per run, and keeps:

* per program span name, over the window (``bench.window``): ``count``
  and the summed numeric ``args`` of the spans that start in it, and the
  ``seconds`` and ``self_seconds`` of every span, clipped to it. Self
  time is a span's time less the union of the other host spans nested in
  it on its thread, the benchmark's ``bench.*`` spans included;
* ``idle``: the device's idle time charged, piece by piece, to the
  innermost span of either the benchmark or the program covering it,
  else to ``host (no span)``; the same idle time ``tracing.reduce``
  charges to the benchmark's spans alone, split further.

A program from before its spans writes none: every reader then finds
nothing and leaves its metric out.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path

from bench import chip, tracing

try:
    from repro.obs import spans as names
except ImportError:          # a program from before its spans: none to keep
    names = None
PROGRAM_PREFIXES = names.PREFIXES if names is not None else ()
KEPT_PREFIXES = (tracing.SPAN_PREFIX, *PROGRAM_PREFIXES)


def load(xplane: Path) -> dict:
    """A trace as ``{"threads": {host line: [(name, start_ns, dur_ns,
    {arg: value})]}, "devices": [Plane]}``: the benchmark's and the
    program's spans on each host thread, and each device's ops."""
    from jax.profiler import ProfileData

    threads: dict[str, list] = {}
    devices = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    lines.setdefault(line.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
            devices.append(tracing.Plane(plane.name, lines))
            continue
        for line in plane.lines:
            kept = [(e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats)) for e in line.events
                    if e.name.startswith(KEPT_PREFIXES)]
            if kept:
                threads.setdefault(line.name, []).extend(kept)
    return {"threads": threads, "devices": devices}


def save(trace: dict, path: Path) -> None:
    """A loaded trace as gzipped JSON, for the recorded trace the tests
    read."""
    with gzip.open(path, "wt") as f:
        json.dump({"threads": trace["threads"],
                   "devices": [{"name": p.name, "lines": p.lines}
                               for p in trace["devices"]]}, f)


def read(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"threads": {k: [tuple(e) for e in v]
                        for k, v in raw["threads"].items()},
            "devices": [tracing.Plane(p["name"],
                                      {k: [tuple(e) for e in v]
                                       for k, v in p["lines"].items()})
                        for p in raw["devices"]]}


def window_of(trace: dict) -> tuple[float, float]:
    spans = [e for events in trace["threads"].values() for e in events
             if e[0] == tracing.WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {tracing.WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    _, start, dur, _ = spans[0]
    return start, start + dur


def self_times(events: list[tuple], lo: float, hi: float) -> list[float]:
    """Each span's time inside ``[lo, hi)`` less the part of it that the
    spans nested in it cover, in ns. ``events`` are the spans of one
    thread, which nest."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    covered: dict[int, list] = {}
    stack: list[int] = []
    for i in order:
        s, e = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            a, b = max(s, lo), min(e, events[p][1] + events[p][2], hi)
            if b > a:
                covered.setdefault(p, []).append((a, b))
        stack.append(i)
    out = []
    for i, (_, s, d, _) in enumerate(events):
        inside = min(s + d, hi) - max(s, lo)
        out.append(inside - sum(b - a for a, b in
                                tracing.union(covered.get(i, [])))
                   if inside > 0 else 0.0)
    return out


def reduce(trace: dict, chips: int) -> dict:
    """``{"spans": {name: {count, seconds, self_seconds, args}}, "idle":
    {innermost span: seconds}}`` over the window; idle time is averaged
    over the first ``chips`` devices."""
    lo, hi = window_of(trace)
    per_name: dict[str, dict] = {}
    covering = []
    for events in trace["threads"].values():
        for (name, s, d, args), own in zip(events,
                                           self_times(events, lo, hi)):
            covering.append((name, s, d))
            inside = min(s + d, hi) - max(s, lo)
            starts_inside = lo <= s < hi
            if not name.startswith(PROGRAM_PREFIXES) or (
                    inside <= 0 and not starts_inside):
                continue
            r = per_name.setdefault(name, {"count": 0, "seconds": 0.0,
                                           "self_seconds": 0.0, "args": {}})
            r["seconds"] += max(inside, 0.0) * 1e-9
            r["self_seconds"] += own * 1e-9
            if starts_inside:
                r["count"] += 1
                for k, v in args.items():
                    if isinstance(v, (int, float)):
                        r["args"][k] = r["args"].get(k, 0) + v
    devs = tracing.device_planes(trace["devices"])[:chips]
    idle: dict[str, float] = {}
    for dev in devs:
        busy = tracing.union(tracing.clip(dev.lines[tracing.OPS_LINE], lo,
                                          hi))
        for label, sec in tracing.charge_gaps(tracing.gaps(busy, lo, hi),
                                              covering).items():
            idle[label] = idle.get(label, 0.0) + sec / len(devs)
    return {"spans": per_name, "idle": idle}


def of_run(run) -> dict:
    """The program's spans of a traced run, by name (``reduce``'s
    ``spans``): read from the run's trace once and kept with it, under
    ``run.trace["program"]``. Empty where the run was not traced or the
    program wrote no span."""
    if run.trace is None:
        return {}
    if "program" not in run.trace:
        try:
            xplane = tracing.find_xplane(chip.TRACE_DIR)
        except FileNotFoundError:
            return {}
        run.trace["program"] = reduce(load(xplane), run.chips)
    return run.trace["program"]["spans"]


def protocol_self_s(spans: dict) -> float:
    """Self seconds of the Thallus control plane's spans
    (``repro.obs.spans.PROTOCOL``)."""
    return sum(spans[n]["self_seconds"] for n in names.PROTOCOL
               if n in spans)
