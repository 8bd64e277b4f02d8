"""Model operations of the decoded rows (``costs.decode_flops``) over the
decode-step program's device time at the chip's peak bf16 FLOP/s."""


def read(ctx):
    n, t = ctx["trace"].modules(r"decode_step")
    if not n or not t:
        return None
    return ctx["counters"]["decode_flops"] / (t * ctx["peaks"]["bf16_flops"]) * 100.0
