"""Device busy ms in the traced window per source row delivered in it."""
from chip_bench.layer_readings import device_ms_per


def read(ctx):
    return device_ms_per(ctx, "source_rows")
