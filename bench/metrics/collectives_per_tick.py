"""Collective leaf ops per simulated tick on the device with the most
(``devtrace.is_collective``; an async pair counts as the trace gives it)."""


def read(ctx):
    trace = ctx["trace"]
    if trace["busy_s"] <= 0:
        return None
    return trace["collectives"] / ctx["ticks"]
