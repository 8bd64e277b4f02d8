"""Share of the traced window in which the device is idle and the host
is inside a serving tick (``serve.tick``) but outside its admissions:
the control plane's and the batcher's own host work."""

import program_spans as P


def read(ctx):
    ticks = P.named(ctx, "serve.tick")
    if not ticks:
        return None
    return P.idle_share(ctx, ticks, outside=P.named(ctx, "serve.admit") or ())
