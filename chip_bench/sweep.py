#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 chip_bench/sweep.py --workload <name> --seed <n> --window 20 \
        --fractions 0.5,0.7,0.85,1.0,1.2

One process builds the cell and warms it up exactly as ``run.py`` does
(the first refit), measures the median service time S of
back-to-back queries, then offers the cell's traffic at each fraction of
1/S for ``--window`` seconds, serving what is left before the next rate. Per rate it prints the offered and
completed counts, the due-time p50/p90 and the backlog at the close. The
knee is the highest rate at which the backlog stays bounded; the cell's
traffic file then fixes about four fifths of it. The benchmark's own runs
never sweep.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# back-to-back queries that measure the median service time
SERVICE_QUERIES = 20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--window", type=float, default=20.0)
    ap.add_argument("--fractions", default="0.5,0.7,0.85,1.0,1.2")
    args = ap.parse_args(argv)

    from chip_bench import harness, stats, traffic

    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(bench, args.workload, ROOT)
    harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    drv, _, _ = harness.build_loadgen(cell, args.seed)
    rec, loop = drv.rec, drv.loop
    drv.warm_up()
    t = time.perf_counter()
    times = []
    for k in range(SERVICE_QUERIES):
        t_k = time.perf_counter()
        drv.submit(f"s{k}", t_k, keep=False)
        while drv.step():
            pass
        times.append(time.perf_counter() - t_k)
    # the median: a query that waits on a refit's compile is no service time
    service_s = float(sorted(times)[len(times) // 2])
    print(json.dumps({"workload": cell.name, "setup_s": t - T_START,
                      "service_ms": service_s * 1e3}), flush=True)
    for frac in (float(f) for f in args.fractions.split(",")):
        rate = frac / service_s
        offsets = traffic.arrival_offsets({"rate_qps": rate}, args.window,
                                          args.seed)
        drv.window = []
        start = len(rec.delivered)
        compiles0 = loop.dispatcher.cache.compile_events
        # distinct qids per rate
        base = f"r{frac}_"
        t0 = drv.rec.clock()
        i, n = 0, len(offsets)
        close = t0 + args.window
        while drv.rec.clock() < close:
            now = drv.rec.clock()
            while i < n and t0 + offsets[i] <= now:
                drv.submit(base + str(i), t0 + offsets[i])
                i += 1
            if not drv.step():
                time.sleep(0.001)
        backlog = (n - i) + rec.undelivered
        done = [q for q in drv.window if rec.delivered.get(q, 1e300) <= close]
        while i < n:
            drv.submit(base + str(i), t0 + offsets[i])
            i += 1
        drv.finish(close + 120)
        lat = [(rec.delivered[q] - drv.due[q]) * 1e3 for q in drv.window]
        print(json.dumps({
            "fraction": frac, "rate_qps": rate, "offered": n,
            "completed_in_window": len(done), "backlog_at_close": backlog,
            "p50_ms": stats.percentile(lat, 50),
            "p90_ms": stats.percentile(lat, 90),
            "compiles": loop.dispatcher.cache.compile_events - compiles0,
            "delivered_total": len(rec.delivered) - start,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
