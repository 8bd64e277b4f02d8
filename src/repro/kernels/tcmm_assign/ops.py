"""Public wrapper for the TCMM assignment kernel."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.platform import resolve_interpret
from repro.kernels.tcmm_assign.kernel import tcmm_assign_fwd


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def tcmm_assign(
    points: jax.Array,     # [N, F]
    centroids: jax.Array,  # [M, F]
    valid: jax.Array,      # [M] bool
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    n, f = points.shape
    bn = min(block_n, n)
    while n % bn != 0:
        bn //= 2
    bn = max(bn, 1)
    return tcmm_assign_fwd(
        points, centroids, valid, block_n=bn,
        interpret=resolve_interpret(interpret),
    )
