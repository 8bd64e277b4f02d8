"""Seeding of the weights, shared by every architecture and by the
program's and the reference's layouts.

Every leaf has its own key, folded from ``--seed``: a leaf inside layer
``layer`` from its index in the architecture's list of a layer's leaves
and from the layer's index (``layer_normal``), a leaf outside the layers
from an index of its own (``top_normal``).  The program gets all layers
at once, made by one jitted call on the device (``program_params``); the
reference makes one layer at a time and gets the same numbers, because
``jax.random`` gives the same values for a key under ``vmap`` as alone.
Each architecture (``bench/archs``) scales these standard normals.
"""

from __future__ import annotations

from types import ModuleType
from typing import Callable, Dict

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def layer_normal(key: jax.Array, idx: int, layer, shape) -> jax.Array:
    """Standard normals for leaf ``idx`` of layer ``layer``, in float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + idx), layer)
    return jax.random.normal(k, shape, jnp.float32)


def top_normal(key: jax.Array, idx: int, shape) -> jax.Array:
    """Standard normals for leaf ``idx`` outside the layers (under 1000),
    in float32."""
    return jax.random.normal(jax.random.fold_in(key, idx), shape, jnp.float32)


def program_params(arch: ModuleType, seed: int, s: Dict, dtype) -> dict:
    """All weights in the program's layout, made on the device in one call."""
    return jax.jit(lambda k: arch.program_tree(k, s, dtype))(base_key(seed))


def leaf_norms(tree, leaf_name: Callable) -> Dict[str, jax.Array]:
    """Norm of each weight by ``leaf_name``, over all layers: of a
    program-layout tree (layers stacked) or of the reference's."""
    sq: Dict[str, jax.Array] = {}
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        k = leaf_name(p)
        sq[k] = sq.get(k, 0.0) + jnp.sum(jnp.square(x.astype(jnp.float32)))
    return {k: jnp.sqrt(v) for k, v in sq.items()}
