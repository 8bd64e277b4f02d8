"""Public wrapper for the fused MoE gating kernel."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.moe_gating.kernel import moe_gating_fwd
from repro.kernels.platform import resolve_interpret


@functools.partial(
    jax.jit, static_argnames=("top_k", "capacity", "block_n", "interpret")
)
def moe_gating(
    logits: jax.Array,  # [N, E]
    top_k: int,
    capacity: int,
    block_n: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (expert_idx [N,k] i32, gates [N,k] f32 renormalized,
    capacity positions [N,k] i32, keep [N,k] bool)."""
    n, e = logits.shape
    if top_k > e:
        raise ValueError(f"top_k={top_k} > num_experts={e}")
    bn = min(block_n, n)
    while n % bn != 0:
        bn //= 2
    bn = max(bn, 1)
    return moe_gating_fwd(
        logits, top_k=top_k, capacity=capacity, block_n=bn,
        interpret=resolve_interpret(interpret),
    )
