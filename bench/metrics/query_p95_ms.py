"""95th percentile of query completion time (call to answer on the
host), over every query of the window (host clock)."""
import numpy as np


def read(ctx):
    w = ctx.window
    if w["kind"] != "closed" or not w["queries"]:
        return None
    return float(np.percentile([b - a for _, a, b in w["queries"]], 95)) * 1e3
