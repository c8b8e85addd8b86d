"""Stream cells: share of the traced window in which the device is idle
outside any running program while the thread that folds is inside one
of the program's spans, in percent (device trace and the program's
spans on its clock; ``spans.host_bound_idle_share``)."""
from bench import spans


def read(ctx):
    if ctx.trace is None or ctx.window["kind"] != "open":
        return None
    return spans.host_bound_idle_share(ctx.trace)
