"""Public wrapper for the SSD chunked scan kernel."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.platform import resolve_interpret
from repro.kernels.ssd_scan.kernel import ssd_chunked_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(
    x: jax.Array,  # [B, T, H, P]
    a: jax.Array,  # [B, T, H]
    B: jax.Array,  # [B, T, N]
    C: jax.Array,  # [B, T, N]
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # [B, H, N, P]
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,T,H,P] f32, final_state [B,H,N,P] f32)."""
    if x.shape[1] % chunk != 0:
        raise ValueError(f"T={x.shape[1]} must be a multiple of chunk={chunk}")
    y, final = ssd_chunked_fwd(
        x, a, B, C, chunk, interpret=resolve_interpret(interpret)
    )
    if initial_state is not None:
        # Fold a nonzero initial state in linearly (the scan is linear in
        # the state): y += C_t * decay_to_t * S0, S_final += decay_T * S0.
        bsz, t, h, p = x.shape
        log_a = jnp.log(jnp.clip(a.astype(jnp.float32), 1e-20))
        cum = jnp.cumsum(log_a, axis=1)  # [B, T, H]
        y = y + jnp.einsum(
            "btn,bth,bhnp->bthp",
            C.astype(jnp.float32),
            jnp.exp(cum),
            initial_state.astype(jnp.float32),
        )
        final = final + jnp.exp(cum[:, -1])[:, :, None, None] * initial_state
    return y, final
