"""Share of the traced window in which device 0 was idle while the host
ran the program's own code (innermost span ``repro.*``, not the callback)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["batches"] or trace["window_s"] <= 0:
        return None
    return trace["idle_in_program_s"] / trace["window_s"]
