"""Set-up time: process start to the first timed call (host clock).

Tables or events made from the seed and put on the device, the program
built, every shape of the mix compiled or read from the cache, warmed.
"""


def read(ctx):
    return ctx.setup_s
