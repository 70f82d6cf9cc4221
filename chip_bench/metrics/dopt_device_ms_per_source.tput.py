"""Device ms in the traced window's direction-switching engines (every
``XLA Modules`` program named ``engine_*_dopt_binned``: the push over the
binned forward slabs and the binned pull) per source row delivered in it.
0.0 where the trace holds no such program."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["source_rows"]:
        return None
    seconds = sum(s for name, s in trace.get("modules", {}).items()
                  if name.endswith("_dopt_binned"))
    return seconds * 1e3 / ctx["source_rows"]
