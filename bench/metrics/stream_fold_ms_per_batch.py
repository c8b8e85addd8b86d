"""Device time of the XLA modules that ``layers/stream_fold.json``
assigns to the streams' folds, over the micro-batches folded in the
traced window (device trace): one batch is folded by every stream."""

LAYER = "stream fold"


def read(ctx):
    if ctx.trace is None or ctx.window["kind"] != "open":
        return None
    batches = ctx.window["offered"] // ctx.traffic["micro_batch"]
    t = ctx.trace["layer_s"].get(LAYER, 0.0)
    if not batches or t <= 0.0:
        return None
    return t / batches * 1e3
