"""The paged latent decode kernel's share of its roofline: the least
time the chip needs for the work the algorithm requires (each live
token's latent row read once, every head's scores over the whole row and
its sum over the latent; the architecture's ``kernel_counters``), the
larger of operations over peak FLOP/s and bytes over peak bandwidth,
over the kernel's own device time.  At decode the bytes bound it.

The kernel's ops are named by its jitted wrapper,
``%_paged_latent_decode_jit.N = ...``; the match is anchored there, as
the op's operands name the latent append that feeds it."""

KERNEL = r"^%?_paged_latent_decode"


def read(ctx):
    c = ctx["counters"]
    if "mla_flops" not in c:
        return None
    n, t = ctx["trace"].ops(KERNEL)
    if not n or not t:
        return None
    p = ctx["peaks"]
    least = max(c["mla_flops"] / p["bf16_flops"], c["mla_bytes"] / p["hbm_bytes_per_s"])
    return least / t * 100.0
