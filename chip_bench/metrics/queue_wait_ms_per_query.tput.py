"""Mean ms a query dispatched in the window waited from its submission to
its batch's launch (``ServingStats.queue_wait_s`` / ``dispatched_queries``)."""
from chip_bench.layer_readings import window_delta


def read(ctx):
    wait = window_delta(ctx, "queue_wait_s")
    queries = window_delta(ctx, "dispatched_queries")
    return wait * 1e3 / queries if wait is not None and queries else None
