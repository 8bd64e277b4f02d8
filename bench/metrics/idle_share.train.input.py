"""Share of the traced window in which the device is idle and the host
is preparing input: cutting batches from the stream (``train.assemble``)
or building a step's batch and dispatching it (``train.upload``)."""

import program_spans as P


def read(ctx):
    if not P.named(ctx, "train.tick"):
        return None
    return P.idle_share(ctx, P.named(ctx, "train.assemble", "train.upload") or ())
