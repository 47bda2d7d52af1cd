"""Seconds JAX spent in set-up tracing, lowering and compiling the K-tick
program, or loading it from the persistent cache (``clock.CompileClock``)."""


def read(ctx):
    return ctx["compile_s"]
