"""Device milliseconds per simulated tick in collective ops: the summed
durations of the collective leaf ops (``devtrace.is_collective``) on the
device with the most, over the ticks in the traced chunks."""


def read(ctx):
    trace = ctx["trace"]
    if trace["busy_s"] <= 0:
        return None
    return trace["collective_s"] * 1e3 / ctx["ticks"]
