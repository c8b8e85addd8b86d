"""Median host time of the harness's own ``PruneStream.fold`` calls in
the traced window (host clock)."""
import numpy as np


def read(ctx):
    w = ctx.window
    if w["kind"] != "open" or not len(w["fold_s"]):
        return None
    return float(np.median(w["fold_s"])) * 1e6
