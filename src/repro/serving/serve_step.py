"""Serving steps: jit'd prefill and single-token decode over the model
zoo's KV caches. These are the functions the dry-run lowers for the
``decode_*`` shape cells and the continuous batcher drives in the live
serving example."""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.zoo import Model

Params = Any


def make_prefill_step(model: Model) -> Callable:
    @jax.jit
    def prefill_step(
        params: Params, batch: Dict[str, jax.Array], cache: Params
    ) -> Tuple[jax.Array, Params]:
        # last_only: unembed a single position, not the whole prompt
        # (the [T, vocab] logits of a long prompt are the largest tensor
        # prefill would otherwise build).
        logits, cache = model.prefill(params, batch, cache, last_only=True)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step


def make_decode_step(model: Model, temperature: float = 0.0) -> Callable:
    """The jitted decode step.  It donates ``cache``: the returned cache
    takes over its buffers, and the paged kernels update the stacked page
    pools in place, so the caller rebinds its cache to the step's output
    and reads no leaf of the one it passed in."""

    @functools.partial(jax.jit, donate_argnames="cache")
    def decode_step(
        params: Params,
        tokens: jax.Array,    # [B, 1] current tokens
        cache: Params,
        positions: jax.Array,  # [B]
        rng: jax.Array,
        frontend: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Params, jax.Array]:
        logits, cache = model.decode_step(
            params, tokens, cache, positions, frontend=frontend
        )
        last = logits[:, -1, :]
        if temperature > 0:
            rng, sub = jax.random.split(rng)
            next_tok = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            next_tok = jnp.argmax(last, axis=-1)
        return next_tok.astype(jnp.int32), cache, rng

    return decode_step
