"""Command R+ as this repository runs it (the program's
``command-r-plus-104b`` preset): its sizes, its weights from the seed in
the program's layout, its plain float32 reference and its costs, for
serving.  The interface is ``common.arch``'s serving half.

The equations are the program's: grouped-query attention (each group of
``heads / kv_heads`` query heads reads one K/V head), rotary embedding
of the two halves of each head, and a parallel block in which attention
and the SwiGLU FFN read the same normed input and add to the residual
together; RMSNorm times ``1 + w``; the tied embedding scaled by
``sqrt(d_model)`` on the way in and used as the unembedding.  The
block's ``norm_ffn`` is in the program's layout and read by nothing.
Weights are scaled as for MiniCPM-2B, whose tied embedding is alike:
output projections at 8 / sqrt(fan_in), the embedding at 0.002.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W
from common import BenchError

OUT_GAIN = 8.0
EMBED_STD = 0.002
NORM_STD = 0.1


def sizes(conf: dict) -> Dict:
    return {
        "d": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "eps": conf["program_departures"]["norm_eps"],
        "rope_theta": conf["program_departures"]["rope_theta"],
    }


def program_arch(conf: dict):
    from repro.config import get_arch

    s = sizes(conf)
    arch = dataclasses.replace(get_arch(conf["arch"], smoke=conf.get("smoke", False)),
                               num_layers=s["layers"])
    have = {"d": arch.d_model, "heads": arch.num_heads, "kv_heads": arch.num_kv_heads,
            "head_dim": arch.resolved_head_dim, "ff": arch.d_ff,
            "vocab": arch.vocab_size, "layers": arch.num_layers,
            "eps": arch.norm_eps, "rope_theta": arch.rope_theta}
    wrong = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if (not arch.tie_embeddings or not arch.parallel_block or arch.logit_softcap
            or arch.residual_scale != 1.0):
        wrong["layout"] = "not a tied parallel-block decoder"
    if wrong:
        raise BenchError(f"the program's {conf['arch']!r} differs from "
                         f"{conf['name']}: {wrong} (program, file)")
    return arch


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def layer_leaves(s):
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


def layer(key, index, s, dtype):
    out = {}
    for i, (name, shape, kind, fan) in enumerate(layer_leaves(s)):
        x = W.layer_normal(key, i, index, shape)
        scale = NORM_STD if kind == "norm" else (
            (OUT_GAIN if kind == "out" else 1.0) / math.sqrt(fan))
        out[name] = (x * scale).astype(dtype)
    return out


def embedding(key, s, dtype):
    return (W.top_normal(key, 1, (s["vocab"], s["d"])) * EMBED_STD).astype(dtype)


def final_norm(key, s, dtype):
    return (W.top_normal(key, 2, (s["d"],)) * NORM_STD).astype(dtype)


def program_tree(key, s, dtype):
    stacked = jax.vmap(lambda i: layer(key, i, s, dtype))(jnp.arange(s["layers"]))
    period = {"norm_attn": stacked["norm_attn"], "norm_ffn": stacked["norm_ffn"],
              "attn": {n: stacked["attn." + n] for n in ("wq", "wk", "wv", "wo")},
              "mlp": {n: stacked["mlp." + n] for n in ("w_gate", "w_up", "w_down")}}
    return {"embed": {"tok": embedding(key, s, dtype)}, "periods": [period],
            "final_norm": final_norm(key, s, dtype)}


def leaf_name(path) -> str:
    keys = [k.key for k in path if hasattr(k, "key")]
    return ".".join(keys[1:]) if keys[0] == "periods" else keys[-1]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(p, x, s, lowp=False):
    """One parallel block over x [N, T, D], causal from position 0."""
    mm = R.mm
    pos = jnp.arange(x.shape[1])
    h = R.rms_norm(x, p["norm_attn"], s["eps"])
    q = rope(mm("ntd,dhk->nthk", h, p["attn.wq"], lowp), pos, s["rope_theta"])
    k = rope(mm("ntd,dhk->nthk", h, p["attn.wk"], lowp), pos, s["rope_theta"])
    v = mm("ntd,dhk->nthk", h, p["attn.wv"], lowp)
    g = s["heads"] // s["kv_heads"]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = mm("nthk,nshk->nhts", q, k, lowp) / math.sqrt(s["head_dim"])
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None], scores, -jnp.inf)
    a = mm("nhts,nshk->nthk", jax.nn.softmax(scores, axis=-1), v, lowp)
    attn = mm("nthk,hkd->ntd", a, p["attn.wo"], lowp)
    gate = mm("ntd,df->ntf", h, p["mlp.w_gate"], lowp)
    up = mm("ntd,df->ntf", h, p["mlp.w_up"], lowp)
    ffn = mm("ntf,fd->ntd", jax.nn.silu(gate) * up, p["mlp.w_down"], lowp)
    return x + attn + ffn


@functools.partial(jax.jit, static_argnames=("sk", "dtype", "lowp"))
def _served_layer(x, key, index, sk, dtype, lowp):
    s = dict(sk)
    p = {n: a.astype(jnp.float32) for n, a in layer(key, index, s, dtype).items()}
    return block(p, x, s, lowp)


def hidden(seed: int, s: Dict, tokens: np.ndarray, dtype, lowp=False):
    key = W.base_key(seed)
    with jax.default_matmul_precision("highest"):
        tok = embedding(key, s, dtype).astype(jnp.float32)
        x = tok[jnp.asarray(tokens)] * math.sqrt(s["d"])
        for i in range(s["layers"]):
            x = _served_layer(x, key, i, R.sizes_key(s), dtype, lowp)
        fn = final_norm(key, s, dtype).astype(jnp.float32)
        return R.rms_norm(x, fn, s["eps"]), tok


# ---------------------------------------------------------------------------
# costs
# ---------------------------------------------------------------------------


def decode_flops(s, rows, kv_tokens):
    d, h, kv, hd, f = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    weights = s["layers"] * (2 * d * h * hd + 2 * d * kv * hd + 3 * d * f) + s["vocab"] * d
    return 2.0 * weights * rows + s["layers"] * 4.0 * h * hd * kv_tokens


def kernel_counters(s, rows, kv_tokens):
    """The paged decode kernel over every layer: q.k and p.v for each
    query head; each live token's K and V read once per K/V head, in
    bf16, and each row's query and output."""
    h, kv, hd, n = s["heads"], s["kv_heads"], s["head_dim"], s["layers"]
    return {"attn_flops": 4.0 * h * hd * kv_tokens * n,
            "attn_bytes": (2.0 * kv_tokens * kv * hd + 2.0 * rows * h * hd) * 2 * n}
