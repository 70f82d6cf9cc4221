"""1 - device busy time (union of op intervals) over the traced window."""
from chip_bench.layer_readings import idle_share


def read(ctx):
    return idle_share(ctx)
