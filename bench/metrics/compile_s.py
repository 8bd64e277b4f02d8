"""Seconds JAX spent tracing, lowering and compiling during set-up
(its own monitoring events, counted by ``common.CompileClock``)."""


def read(ctx):
    return ctx.get("compile_s")
