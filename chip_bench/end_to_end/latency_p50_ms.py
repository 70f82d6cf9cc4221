"""Median latency of every query due in the window, from its due time to
its delivery (answers that come after the close count with their wait)."""
from chip_bench.stats import percentile


def read(ctx):
    return percentile(ctx["latencies_ms"], 50)
