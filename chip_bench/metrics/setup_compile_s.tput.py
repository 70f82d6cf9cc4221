"""Host seconds of set-up spent in launches that compiled or loaded an
engine (``EngineCache.compile_s`` as the window opens)."""


def read(ctx):
    return ctx["program"]["open"].get("compile_s")
