"""Result leaves a batch read to the host with no copy enqueued at launch
(``SchedulerStats.d2h_blocking``: a full round trip each) in the window."""
from chip_bench.layer_readings import window_delta


def read(ctx):
    blocking = window_delta(ctx, "d2h_blocking")
    if blocking is None or not ctx["batches"]:
        return None
    return blocking / ctx["batches"]
