"""Median host time of a serving tick that decoded: the ``serve.tick``
span less the ``serve.admit`` and ``serve.token_wait`` spans it holds,
in which the host waits on the device."""

import statistics

import program_spans as P

WAITS = ("serve.admit", "serve.token_wait")


def read(ctx):
    ticks = P.named(ctx, "serve.tick")
    if not ticks:
        return None
    held = P.within(ticks, P.named(ctx, "serve.decode", *WAITS) or [])
    host = [t.ms - sum(c.ms for c in h if c.name in WAITS)
            for t, h in zip(ticks, held) if any(c.name == "serve.decode" for c in h)]
    return statistics.median(host) if host else None
