"""Stream cells: 1 - (union of device operation intervals) / (traced
window), in percent (device trace)."""


def read(ctx):
    if ctx.trace is None or ctx.window["kind"] != "open":
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
