"""The 95th percentile of time to first token in a cell offered more
than the system sustains: every request due in the window, one with no
token by its end counted to the end (``serve.end_to_end``).  The queue
grows all through such a run, so this tail swings with the smallest
change and is recorded, not judged."""


def read(ctx):
    return ctx["end_to_end"].get("ttft_p95_ms")
