#!/usr/bin/env python3
"""Read the correctness check for many seeds, with the control beside it.

    python3 chip_bench/control.py --workload <name> --seeds 1,2,3 --seconds 15

Runs the cell once per seed in one process, as ``run.py`` does, and for
each prints one JSON line: the program's checks, and the control's reading
on the same compared queries. The control is the reference with every
adjacency row cut to its first ``oracle.CONTROL_ROW_CAP`` slots; it must
read as not correct. The benchmark's own runs never run it.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chip_bench import harness

    cell = harness.load_cell(harness.load_benchmark(ROOT), args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(cell, seed, args.seconds, False,
                             time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "metrics": r["metrics"], "device": r["device"],
                          "control": r["control"], "checks": r["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
