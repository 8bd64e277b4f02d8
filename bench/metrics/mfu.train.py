"""Forward and backward operations per token (the architecture's
``train_flops_per_token``) times the tokens trained in the traced
window, over the window's seconds, the chips and the chip's peak bf16
FLOP/s."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("tokens"):
        return None
    flops = c["flops_per_token"] * c["tokens"]
    return flops / (ctx["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops"]) * 100.0
