"""Closed loop: rows of every table each completed query reads, summed,
over the window's time (host clock). The window ends when the query in
flight at the deadline has finished."""


def read(ctx):
    w = ctx.window
    if w["kind"] != "closed" or not w["queries"]:
        return None
    rows = sum(ctx.work[name]["rows"] for name, _, _ in w["queries"])
    return rows / (w["end"] - w["start"])
