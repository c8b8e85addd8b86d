"""Open loop: events whose keep decisions were on the host inside the
window, over the window's time. At a rate the system sustains it equals
the offered rate; below it, the system fell behind."""


def read(ctx):
    w = ctx.window
    if w["kind"] != "open":
        return None
    return w["delivered"] / w["seconds"]
