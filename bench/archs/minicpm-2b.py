"""MiniCPM-2B as this repository runs it: its sizes, its weights from the
seed in the program's layout, its plain float32 reference, and the
operations and bytes it needs.  The interface is ``common.arch``'s.

Sizes.  ``sizes`` reads only the configuration file.  ``program_arch``
builds the program's own ``minicpm-2b`` preset at the file's depth and
refuses one whose sizes differ, so a later change to the program's
presets cannot silently change a cell.

Weights.  A layer's leaves are ``layer_leaves``, each seeded by
``weights.layer_normal``.  The scales are those of the program's own
initialiser with two changes: the output projections ``wo`` and
``w_down`` are ``OUT_GAIN`` times larger, and the tied embedding is
``EMBED_STD`` (not 0.02).  The program embeds with ``tok * sqrt(d_model)``
and unembeds with the same table, so at the initialiser's scales the
residual stream stays close to the input token's embedding and the
largest logit is that token's: served tokens repeated the one before
them 99% of the time on a TPU v5e, and a wrong attention or cache would
still pick the same token.  With these scales the layers set the
residual stream (no position of a random prompt put its own token
first, at the published widths on the CPU), and the served tokens
depend on the whole prompt.

Reference.  Written from the equations, in ``jax.numpy``, with every
matrix product at ``Precision.HIGHEST``; it imports nothing of the
program, and makes one layer's weights at a time.  The equations are
the program's, which depart from the published MiniCPM-2B in four places
(each listed in the configuration files under ``program_departures``):
the input embedding is scaled by ``sqrt(d_model)`` (published:
``scale_emb`` 12), the logits are not divided by ``d_model /
dim_model_base``, RMSNorm multiplies by ``1 + w`` and uses eps 1e-6
(published: ``w``, 1e-5).

Costs.  Operations and bytes that the algorithm needs, from shapes
alone: the live K/V tokens of each slot (not whole pages), one read of
each weight, and matrix products at two operations per multiply-add.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W
from common import BenchError

# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(conf: dict) -> Dict:
    dep = conf["program_departures"]
    published_layers = conf["published"]["num_hidden_layers"]
    return {
        "d": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["hidden_size"] // conf["num_attention_heads"],
        "ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "residual_scale": conf["scale_depth"] / math.sqrt(published_layers),
        "eps": dep["rms_norm_eps"],
        "rope_theta": conf["rope_theta"],
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
    if not math.isclose(arch.residual_scale, s["residual_scale"], rel_tol=1e-9):
        wrong["residual_scale"] = (arch.residual_scale, s["residual_scale"])
    if not arch.tie_embeddings or arch.logit_softcap or arch.parallel_block:
        wrong["layout"] = "not a tied, plain pre-norm decoder"
    if wrong:
        raise BenchError(f"the program's {conf['arch']!r} differs from "
                         f"{conf['name']}: {wrong} (program, file)")
    return arch


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

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


def _leaf(key, idx, layer, shape, kind, fan_in):
    x = W.layer_normal(key, idx, layer, shape)
    if kind == "norm":
        return x * NORM_STD
    gain = OUT_GAIN if kind == "out" else 1.0
    return x * (gain / math.sqrt(fan_in))


def layer(key: jax.Array, index, s: Dict[str, int], dtype) -> Dict[str, jax.Array]:
    """One layer's weights, ``{"attn.wq": ..., ...}``, in ``dtype``."""
    return {name: _leaf(key, i, index, shape, kind, fan).astype(dtype)
            for i, (name, shape, kind, fan) in enumerate(layer_leaves(s))}


def embedding(key: jax.Array, s: Dict[str, int], dtype) -> jax.Array:
    return (W.top_normal(key, 1, (s["vocab"], s["d"])) * EMBED_STD).astype(dtype)


def final_norm(key: jax.Array, s: Dict[str, int], dtype) -> jax.Array:
    return (W.top_normal(key, 2, (s["d"],)) * NORM_STD).astype(dtype)


def program_tree(key: jax.Array, s: Dict[str, int], dtype) -> dict:
    """All weights in the program's layout (one scanned period of one
    layer kind, stacked over ``layers``), under a caller's ``jit``."""
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


def leaf_name(path) -> str:
    """A leaf's name as the reference names it, in either layout:
    ``['periods'][0]['attn']['wq']`` and ``['layers'][3]['attn.wq']`` ->
    ``attn.wq``, ``['embed']['tok']`` -> ``tok``."""
    keys = [k.key for k in path if hasattr(k, "key")]
    if keys[0] == "periods":
        return ".".join(keys[1:])
    return keys[-1]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def rope(x, pos, theta):
    """x [N, T, H, D], pos [T]: rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(p: Dict[str, jax.Array], x, s, lowp=False):
    """One decoder layer over x [N, T, D], causal from position 0."""
    mm, rms_norm = R.mm, R.rms_norm
    n, t, _ = x.shape
    pos = jnp.arange(t)
    rs = s["residual_scale"]
    h = rms_norm(x, p["norm_attn"], s["eps"])
    q = rope(mm("ntd,dhk->nthk", h, p["attn.wq"], lowp), pos, s["rope_theta"])
    k = rope(mm("ntd,dhk->nthk", h, p["attn.wk"], lowp), pos, s["rope_theta"])
    v = mm("ntd,dhk->nthk", h, p["attn.wv"], lowp)
    g = s["heads"] // s["kv_heads"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = mm("nthk,nshk->nhts", q, k, lowp) / math.sqrt(s["head_dim"])
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = mm("nhts,nshk->nthk", probs, v, lowp)
    x = x + rs * mm("nthk,hkd->ntd", a, p["attn.wo"], lowp)
    h = rms_norm(x, p["norm_ffn"], s["eps"])
    gate = mm("ntd,df->ntf", h, p["mlp.w_gate"], lowp)
    up = mm("ntd,df->ntf", h, p["mlp.w_up"], lowp)
    return x + rs * mm("ntf,fd->ntd", jax.nn.silu(gate) * up, p["mlp.w_down"], lowp)


def embed(tok, tokens, s):
    return tok[tokens] * math.sqrt(s["d"])


@functools.partial(jax.jit, static_argnames=("sk", "dtype", "lowp"))
def _served_layer(x, key, index, sk, dtype, lowp):
    s = dict(sk)
    p = {n: a.astype(jnp.float32) for n, a in layer(key, index, s, dtype).items()}
    return block(p, x, s, lowp)


def hidden(seed: int, s: Dict, tokens: np.ndarray, dtype, lowp=False):
    """Final-norm hidden states [N, T, D] and the float32 unembedding
    table (the tied embedding), for the weights as served in ``dtype``."""
    key = W.base_key(seed)
    with jax.default_matmul_precision("highest"):
        tok = embedding(key, s, dtype).astype(jnp.float32)
        x = embed(tok, jnp.asarray(tokens), s)
        for i in range(s["layers"]):
            x = _served_layer(x, key, i, R.sizes_key(s), dtype, lowp)
        fn = final_norm(key, s, dtype).astype(jnp.float32)
        return R.rms_norm(x, fn, s["eps"]), tok


def train_params(seed: int, s: Dict) -> Dict:
    key = W.base_key(seed)
    return {"tok": embedding(key, s, jnp.float32),
            "final_norm": final_norm(key, s, jnp.float32),
            "layers": [layer(key, i, s, jnp.float32) for i in range(s["layers"])]}


def nll_sum(params, tokens, labels, sk, lowp):
    s = dict(sk)
    x = embed(params["tok"], tokens, s)
    for p in params["layers"]:
        x = block(p, x, s, lowp)
    x = R.rms_norm(x, params["final_norm"], s["eps"])
    logits = R.mm("ntd,vd->ntv", x, params["tok"], lowp)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


# ---------------------------------------------------------------------------
# costs
# ---------------------------------------------------------------------------


def matmul_params(s: dict) -> int:
    """Weights that every token multiplies: each layer's projections and
    the (tied) unembedding; the embedding lookup is not a product."""
    d, h, kv, hd, f = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return s["layers"] * per_layer + s["vocab"] * d


def paged_attention_flops(s: dict, kv_tokens: int) -> float:
    """One layer's decode attention over ``kv_tokens`` live K/V tokens
    summed over the rows: q.k and p.v, per query head."""
    return 4.0 * s["heads"] * s["head_dim"] * kv_tokens


def paged_attention_bytes(s: dict, kv_tokens: int, rows: int, itemsize: int) -> float:
    """One layer's decode attention: every live K and V token read once,
    each row's query read and output written once."""
    kv = 2.0 * kv_tokens * s["kv_heads"] * s["head_dim"]
    qo = 2.0 * rows * s["heads"] * s["head_dim"]
    return (kv + qo) * itemsize


def decode_flops(s: dict, rows: int, kv_tokens: int) -> float:
    """Model operations of ``rows`` decoded tokens whose attention spans
    ``kv_tokens`` live tokens in all."""
    return 2.0 * matmul_params(s) * rows + s["layers"] * paged_attention_flops(s, kv_tokens)


def kernel_counters(s: dict, rows: int, kv_tokens: int) -> dict:
    """The paged decode-attention kernel's work over every layer, read by
    ``paged_decode_attention_roofline``: K and V in bf16, as served."""
    return {
        "attn_flops": paged_attention_flops(s, kv_tokens) * s["layers"],
        "attn_bytes": paged_attention_bytes(s, kv_tokens, rows, 2) * s["layers"],
    }


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token (three times
    the forward), causal attention over half the sequence on average;
    recomputation is not counted."""
    attn = s["layers"] * 4.0 * s["heads"] * s["head_dim"] * (seq_len + 1) / 2
    return 3.0 * (2.0 * matmul_params(s) + attn)
