"""Median wall time of an admission: the program's ``serve.admit`` span
(the prefill, the page merge and the wait for the first token)."""

import statistics

import program_spans as P


def read(ctx):
    found = P.named(ctx, "serve.admit")
    return statistics.median(s.ms for s in found) if found else None
