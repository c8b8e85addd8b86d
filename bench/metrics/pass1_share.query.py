"""Query cells: device time of the XLA modules that
``layers/engine_pass1.json`` assigns to the engine's pass 1, as a share
of the traced window, in percent (device trace). Unlike
``pass1_ms_per_query`` it reads a slice of a query too."""

LAYER = "engine pass 1"


def read(ctx):
    if ctx.trace is None or ctx.window["kind"] != "closed":
        return None
    t = ctx.trace["layer_s"].get(LAYER, 0.0)
    if t <= 0.0:
        return None
    return 100.0 * t / ctx.trace["window_s"]
