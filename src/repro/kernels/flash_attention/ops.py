"""Public wrapper for the flash attention kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.platform import resolve_interpret


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "sm_scale", "q_offset", "block_q", "block_k",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    causal: bool = True,
    window: int = 0,
    sm_scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("q,k,v must be [B, T|S, H|Hkv, D]")
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError("num_heads must be a multiple of num_kv_heads")
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    return flash_attention_fwd(
        q, k, v,
        causal=causal, window=window, sm_scale=sm_scale, q_offset=q_offset,
        block_q=bq, block_k=bk, interpret=resolve_interpret(interpret),
    )
