"""Seconds JAX spent inside the window compiling, loading from the compile
cache, tracing and lowering (its own monitoring events)."""


def read(ctx):
    c = ctx["compile"]
    return c["compile_secs"] + c["trace_secs"]
