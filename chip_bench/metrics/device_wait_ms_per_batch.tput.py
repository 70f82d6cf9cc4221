"""Host ms a batch waiting on the device (``repro.dispatch.wait_device``)
over the traced window."""
from chip_bench.layer_readings import span_ms_per_batch


def read(ctx):
    return span_ms_per_batch(ctx, "device_wait")
