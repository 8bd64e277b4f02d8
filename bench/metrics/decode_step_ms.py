"""Device time per run of the jitted decode-step program, from the trace."""


def read(ctx):
    n, t = ctx["trace"].modules(r"decode_step")
    return t / n * 1e3 if n else None
