"""Engine compile events (``EngineCache.compile_events``) inside the window."""


def read(ctx):
    return ctx["compiles"]
