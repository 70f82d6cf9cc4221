#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chip_bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit. With no TPU, or fewer chips than the cell asks
for, it exits non-zero and prints no result; so it does when the window has
not opened ``harness.SETUP_DEADLINE_S`` seconds after the process started,
after naming the set-up stage it reached.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chip_bench import harness

    watchdog = harness.SetupWatchdog(harness.SETUP_DEADLINE_S, T_START)
    watchdog.reached("cell")
    cell = harness.load_cell(harness.load_benchmark(ROOT), args.workload, ROOT)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START,
        trace_dir=ROOT / ".bench_trace" / cell.name, watchdog=watchdog,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
