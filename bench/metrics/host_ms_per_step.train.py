"""Host wall time per optimizer step: the training ticks (``train.tick``)
less the host's block on each step's loss (``train.loss_wait``), over the
optimizer steps (``train.step``) in the window."""

import program_spans as P


def read(ctx):
    found = P.named(ctx, "train.tick", "train.loss_wait", "train.step") or []
    steps = sum(s.name == "train.step" for s in found)
    if not steps:
        return None
    ms = {n: sum(s.ms for s in found if s.name == n) for n in ("train.tick", "train.loss_wait")}
    return (ms["train.tick"] - ms["train.loss_wait"]) / steps
