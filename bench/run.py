"""Run one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix and metrics are found by name (see ``bench/harness.py``). The run
builds the deployment from ``--seed`` and warms every shape its traffic
uses (set-up), measures for ``--seconds`` seconds, then checks what the
window produced against the plain reference. The last line of stdout is
the result as one JSON object; the numbers compared, each with its limit,
are the last lines of stderr. ``--trace 1`` profiles the window and
reports the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result: there is no CPU fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse                    # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402
from pathlib import Path           # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
# JAX's compile cache at a fixed path inside the checkout (the program's
# enable_compile_cache takes it from here), and the TPU runtime's logs
# inside it too
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", str(CHECKOUT / "bench_out" / "tpu_logs"))

from bench import chip, harness    # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = harness.find_cell(args.workload)
        devices = chip.accelerators(cell.chips)
        session = chip.Session(cell, args.seed, args.seconds,
                               bool(args.trace), devices, T_START)
        line, checks = session.run()
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.emit(line, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
