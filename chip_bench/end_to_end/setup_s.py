"""Set-up time: process start to the start of the window (graph, operands,
compilation or cache loads, warm-up through the first refit)."""


def read(ctx):
    return ctx["setup_s"]
