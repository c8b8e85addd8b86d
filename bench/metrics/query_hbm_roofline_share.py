"""Bytes of the held columns the traced queries must read once (those
their ``queries/<name>.py`` binds, from shapes and dtypes), over the
device's HBM peak (``peaks/<kind>.json``), over the device's busy time:
the share of the least time the chip could take for that work, in
percent. It reads the
same work whatever implements the query."""


def read(ctx):
    if (ctx.trace is None or ctx.window["kind"] != "closed"
            or "cut_s" in ctx.window):   # a slice of a query: none completed
        return None
    if ctx.peaks is None:
        raise KeyError("no peaks/<kind>.json for this device kind")
    nbytes = sum(ctx.work[name]["bytes"] for name, _, _ in ctx.window["queries"])
    least_s = nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace["busy_s"]
