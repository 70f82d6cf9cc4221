"""Host ms a batch finalizing results (``repro.serve.finalize`` less the
caller's ``repro.serve.callback``) over the traced window."""
from chip_bench.layer_readings import span_ms_per_batch


def read(ctx):
    return span_ms_per_batch(ctx, "finalize")
