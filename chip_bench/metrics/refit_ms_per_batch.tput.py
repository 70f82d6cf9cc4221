"""Host ms a batch in threshold refits and their cost probes
(``repro.dispatch.refit``) over the traced window."""
from chip_bench.layer_readings import span_ms_per_batch


def read(ctx):
    return span_ms_per_batch(ctx, "refit")
