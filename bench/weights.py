"""Weights made from ``--seed``, one generator for the program and the reference.

Every leaf of every layer has its own key, folded from the seed, the
leaf's index and the layer's index.  The program gets all layers at
once, made by one jitted call on the device (``program_params``); the
reference makes one layer at a time (``layer``) and gets the same
numbers, because ``jax.random`` gives the same values for a key under
``vmap`` as alone.

The scales are those of the program's own initialiser with two
changes: the output projections ``wo`` and ``w_down`` are ``OUT_GAIN``
times larger, and the tied embedding is ``EMBED_STD`` (not 0.02).  The
program embeds with ``tok * sqrt(d_model)`` and unembeds with the same
table, so at the initialiser's scales the residual stream stays close
to the input token's embedding and the largest logit is that token's:
served tokens repeated the one before them 99% of the time on a TPU v5e,
and a wrong attention or cache would still pick the same token.  With
these scales the layers set the residual stream (no position of a
random prompt put its own token first, at the published widths on the
CPU), and the served tokens depend on the whole prompt.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

OUT_GAIN = 8.0
EMBED_STD = 0.002
NORM_STD = 0.1

# (name, shape from sizes, kind): kind "norm", "in" (1/sqrt(fan_in)) or
# "out" (OUT_GAIN/sqrt(fan_in)).
def layer_leaves(s: Dict[str, int]) -> List[Tuple[str, tuple, str, int]]:
    d, h, kv, hd, f = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    return [
        ("norm_attn", (d,), "norm", 1),
        ("attn.wq", (d, h, hd), "in", d),
        ("attn.wk", (d, kv, hd), "in", d),
        ("attn.wv", (d, kv, hd), "in", d),
        ("attn.wo", (h, hd, d), "out", h * hd),
        ("norm_ffn", (d,), "norm", 1),
        ("mlp.w_gate", (d, f), "in", d),
        ("mlp.w_up", (d, f), "in", d),
        ("mlp.w_down", (f, d), "out", f),
    ]


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _leaf(key, idx, layer, shape, kind, fan_in):
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + idx), layer)
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        return x * NORM_STD
    gain = OUT_GAIN if kind == "out" else 1.0
    return x * (gain / math.sqrt(fan_in))


def layer(key: jax.Array, index, s: Dict[str, int], dtype) -> Dict[str, jax.Array]:
    """One layer's weights, ``{"attn.wq": ..., ...}``, in ``dtype``."""
    return {name: _leaf(key, i, index, shape, kind, fan).astype(dtype)
            for i, (name, shape, kind, fan) in enumerate(layer_leaves(s))}


def embedding(key: jax.Array, s: Dict[str, int], dtype) -> jax.Array:
    return (jax.random.normal(jax.random.fold_in(key, 1), (s["vocab"], s["d"]),
                              jnp.float32) * EMBED_STD).astype(dtype)


def final_norm(key: jax.Array, s: Dict[str, int], dtype) -> jax.Array:
    return (jax.random.normal(jax.random.fold_in(key, 2), (s["d"],), jnp.float32)
            * NORM_STD).astype(dtype)


def program_params(seed: int, s: Dict[str, int], dtype) -> dict:
    """All weights in the program's layout (one scanned period of one
    layer kind, stacked over ``layers``), made on the device in one call."""
    return jax.jit(lambda k: program_tree(k, s, dtype))(base_key(seed))


def program_tree(key: jax.Array, s: Dict[str, int], dtype) -> dict:
    """``program_params`` under a caller's ``jit``."""
    stacked = jax.vmap(lambda i: layer(key, i, s, dtype))(
        jnp.arange(s["layers"]))
    period = {"norm_attn": stacked["norm_attn"],
              "norm_ffn": stacked["norm_ffn"],
              "attn": {n: stacked["attn." + n]
                       for n in ("wq", "wk", "wv", "wo")},
              "mlp": {n: stacked["mlp." + n]
                      for n in ("w_gate", "w_up", "w_down")}}
    return {"embed": {"tok": embedding(key, s, dtype)},
            "periods": [period],
            "final_norm": final_norm(key, s, dtype)}


def canonical(path) -> str:
    """A leaf's name as the reference names it, in either layout:
    ``['periods'][0]['attn']['wq']`` and ``['layers'][3]['attn.wq']`` ->
    ``attn.wq``, ``['embed']['tok']`` -> ``tok``."""
    keys = [k.key for k in path if hasattr(k, "key")]
    if keys[0] == "periods":
        return ".".join(keys[1:])
    return keys[-1]


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Norm of each weight by canonical name, over all layers: of a
    program-layout tree (layers stacked) or of the reference's (a list
    of layers)."""
    sq: Dict[str, jax.Array] = {}
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        k = canonical(p)
        sq[k] = sq.get(k, 0.0) + jnp.sum(jnp.square(x.astype(jnp.float32)))
    return {k: jnp.sqrt(v) for k, v in sq.items()}
