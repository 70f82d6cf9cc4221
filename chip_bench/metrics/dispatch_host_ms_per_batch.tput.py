"""Host ms a batch in the dispatcher's own code (self time of
``repro.dispatch.begin`` and ``repro.dispatch.settle``) over the traced
window."""
from chip_bench.layer_readings import span_ms_per_batch


def read(ctx):
    return span_ms_per_batch(ctx, "dispatch_host")
