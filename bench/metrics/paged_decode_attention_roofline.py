"""The paged decode-attention kernel's share of its roofline: the least
time the chip needs for the work the algorithm requires (live K/V tokens
read once, q.k and p.v; the architecture's ``kernel_counters``), the
larger of operations over peak FLOP/s and bytes over peak bandwidth,
over the kernel's own device time.  At decode the bytes bound it."""

KERNEL = r"paged_decode(?!.*append)"


def read(ctx):
    n, t = ctx["trace"].ops(KERNEL)
    if not n or not t:
        return None
    c, p = ctx["counters"], ctx["peaks"]
    least = max(c["attn_flops"] / p["bf16_flops"], c["attn_bytes"] / p["hbm_bytes_per_s"])
    return least / t * 100.0
