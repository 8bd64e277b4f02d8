"""Median time from a request's due time to its admission into a slot,
seen from the batcher's slots after each ``pool.step``."""

import statistics


def read(ctx):
    waits = ctx["counters"].get("queue_wait_s")
    return statistics.median(waits) * 1e3 if waits else None
