#!/usr/bin/env python3
"""Median and spread of each metric over result lines of repeated runs.

    python3 chip_bench/spread.py set_a/*.out -- set_b/*.out

Each file's last line is a result of ``run.py``. Per set and metric it
prints the median and the spread (interquartile distance over the median,
``statistics.quantiles(values, n=4)``), and the spread without the run
farthest from the median; with two sets, the wider spread and five times
it, the bound that spread suggests.
"""
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chip_bench.stats import spread  # noqa: E402


def load(paths) -> dict:
    out: dict = {}
    for p in paths:
        lines = Path(p).read_text().strip().splitlines()
        r = json.loads(lines[-1])
        for name, m in r["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def describe(values) -> dict:
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return {"n": len(values), "median": med, "spread": spread(values),
            "spread_without_farthest": spread(rest) if len(rest) >= 2 else None}


def main(argv) -> int:
    sets = [[]]
    for a in argv:
        if a == "--":
            sets.append([])
        else:
            sets[-1].append(a)
    loaded = [load(s) for s in sets if s]
    for name in sorted(loaded[0]):
        rows = [describe(s[name]) for s in loaded if name in s]
        wide = max(r["spread"] for r in rows)
        print(json.dumps({"metric": name, "sets": rows, "widest": wide,
                          "five_times": 5 * wide}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
