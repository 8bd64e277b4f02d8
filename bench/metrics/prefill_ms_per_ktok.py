"""Device time of the prefill programs per 1,000 prompt tokens admitted
in the traced window."""


def read(ctx):
    n, t = ctx["trace"].modules(r"prefill_step")
    tokens = ctx["counters"].get("prefill_tokens", 0)
    return t / tokens * 1e6 if n and tokens else None
