"""Open loop: 95th percentile, over every event of the window, of the
time from the event's due time until every stream's live keep decisions
for its micro-batch are on the host (host clock)."""
import numpy as np


def read(ctx):
    w = ctx.window
    if w["kind"] != "open" or not w["offered"]:
        return None
    return float(np.percentile(w["latency_s"], 95)) * 1e3
