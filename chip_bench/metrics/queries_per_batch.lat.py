"""Queries delivered in the window per batch the loop dispatched in it."""
from chip_bench.layer_readings import per_batch


def read(ctx):
    return per_batch(ctx, "completed")
