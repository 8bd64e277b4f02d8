"""Device time per run of the jitted train-step program, from the trace."""


def read(ctx):
    n, t = ctx["trace"].modules(r"train_step")
    return t / n * 1e3 if n else None
