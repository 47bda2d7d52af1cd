"""Device milliseconds per simulated tick: the union of the device's op
intervals in the traced chunks (mean over the chips) over the ticks in them."""


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    return busy * 1e3 / ctx["ticks"] if busy > 0 else None
