"""Device time of the XLA modules that ``layers/engine_pass1.json``
assigns to the engine's pass 1, over the queries completed in the traced
window (device trace)."""

LAYER = "engine pass 1"


def read(ctx):
    if (ctx.trace is None or ctx.window["kind"] != "closed"
            or "cut_s" in ctx.window):   # a slice of a query: none completed
        return None
    n = len(ctx.window["queries"])
    t = ctx.trace["layer_s"].get(LAYER, 0.0)
    if not n or t <= 0.0:
        return None
    return t / n * 1e3
