"""Readings of the control and of planted faults, on the chip, at a cell's
own size: what ``correct``'s limits were set against. The benchmark's own
runs never run this.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] \
        [--seconds <s>]

Scan cells: the cell's window (``--seconds``, at its own load) with the
control in the program's place: the reference's own answers with the
decimal columns held in bfloat16. Prints the numbers compared.

Feed cell (no window needed): for each seed, the reference's readings in
float32 at the highest matmul precision, and against them the numbers of
the control (the same reference in bfloat16) and of two planted faults:
half of each batch left out (the mean over the rest), and one token of the
first batch altered. A state left unchanged reads 1 by the gap's measure
and needs no run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import chip, harness, weights    # noqa: E402


def feed_readings(cell: harness.Cell, seed: int) -> dict:
    import numpy as np

    feed = harness.generator_module(cell.traffic)
    kind = harness.kind_module(cell.config)
    reference = harness.reference_module(cell.config)
    traffic, cfg = cell.traffic, cell.config
    rows = kind.make_corpus(traffic, weights.dims(cfg)["V"], seed)
    steps = [feed.batch_rows(rows, k, traffic["batch_seqs"])
             for k in range(1, traffic["checked_steps"] + 1)]
    half = [(t[:len(t) // 2], l[:len(l) // 2]) for t, l in steps]
    altered = [(t.copy(), l.copy()) for t, l in steps]
    t, l = altered[0]
    pos = t.shape[1] // 2
    t[0, pos] = (t[0, pos] + 1) % weights.dims(cfg)["V"]
    l[0, pos - 1] = t[0, pos]
    ref = reference.readings(cfg, seed, steps)
    out = {"reference": ref}
    for name, variant, kw in (
            ("control_bf16", steps, {"dtype": "bfloat16",
                                     "precision": "default"}),
            ("fault_half_batch", half, {}),
            ("fault_token_altered", altered, {})):
        out[name] = feed.numbers(reference.readings(cfg, seed, variant, **kw),
                                 ref)
    return out


def scan_readings(cell: harness.Cell, seed: int, seconds: float,
                  devices) -> dict:
    session = chip.Session(cell, seed, seconds, False, devices,
                           time.perf_counter())
    session.run_record.peaks = harness.peaks_for(devices[0].device_kind)
    out = harness.generator_module(cell.traffic).run(
        session, control=cell.config["decimals"])
    return {"control_bf16_decimals": out["numbers"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    cell = harness.find_cell(args.workload)
    devices = chip.accelerators(cell.chips)
    chip.enable_compile_cache()
    for seed in args.seeds:
        if cell.traffic["generator"] == "train_feed":
            got = feed_readings(cell, seed)
        else:
            got = scan_readings(cell, seed, args.seconds, devices)
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
