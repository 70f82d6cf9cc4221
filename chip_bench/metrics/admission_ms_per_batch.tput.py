"""Host ms a batch in the admission plan (self time of
``repro.admission.plan``) over the traced window."""
from chip_bench.layer_readings import span_ms_per_batch


def read(ctx):
    return span_ms_per_batch(ctx, "admission")
