"""Share of the traced window in which the device is idle and the host
is inside an admission (``serve.admit``)."""

import program_spans as P


def read(ctx):
    if not P.named(ctx, "serve.tick"):
        return None
    return P.idle_share(ctx, P.named(ctx, "serve.admit") or ())
