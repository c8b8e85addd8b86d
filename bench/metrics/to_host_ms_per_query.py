"""Query cells: host time inside the program's ``*.to_host`` spans (the
device->host reads the master needs, with any wait for the device work
behind them), over the queries of the traced window (program spans;
``spans.stage_ms_per_query``)."""
from bench import spans


def read(ctx):
    if (ctx.trace is None or ctx.window["kind"] != "closed"
            or "cut_s" in ctx.window):   # a slice: its queries are not whole
        return None
    return spans.stage_ms_per_query(ctx.trace, "to_host")
