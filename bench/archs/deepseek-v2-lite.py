"""DeepSeek-V2-Lite as this repository serves it: one chip's share of an
8-way expert-parallel deployment.  Its sizes, its weights from the seed
in the program's layout, its plain float32 reference and the operations
and bytes it needs.  The interface is ``common.arch``'s serving half.

Sizes.  ``sizes`` reads only the configuration file: the published
widths, and of the routed experts the ``n_routed_experts`` this chip
holds (from ``deployment.first_held_expert``) beside the published count
that the router scores.  ``program_arch`` builds the program's own
``deepseek-v2-lite`` preset at the file's depth and refuses one whose
sizes differ.

Weights.  Each layer's leaves are seeded by ``weights.layer_normal``; a
routed expert's by its index among all the published experts, so two
chips' shares hold different experts.  Scales are the program
initialiser's but for the output projections (``wo``, and every
``w_down``: the dense layer's, the shared experts' and the routed
experts'), at ``OUT_GAIN`` times it, the embedding (``EMBED_STD``, small,
so that the layers and not the input token set the residual stream) and
the norms (``NORM_STD``).  A larger gain on the routed experts alone
(64) made the served tokens hinge on near-ties at the top-k boundary: in
bfloat16 the program and the float32 reference then picked different
experts for a few tokens and their logits lay up to 2 standard
deviations apart at smoke size.

Reference.  Written from the equations (arXiv:2405.04434, the published
modeling code's ``DeepseekV2Attention`` and ``DeepseekV2MoE``) in
``jax.numpy``, every matrix product at ``Precision.HIGHEST``; it imports
nothing of the program and makes one layer's weights at a time.  Latent
attention is computed as published, with keys and values up-projected
from the normed latent per head, queries in blocks of ``Q_BLOCK`` so that
8 sequences of 7.7k tokens fit one chip; YaRN frequencies, and the
softmax scale ``(qk_nope + qk_rope)^-0.5 * mscale^2``.  The MoE layer
scores all published experts (softmax in float32, greedy top-k, no
renormalisation, times the scaling factor), adds what the held experts
give for the tokens routed to them, and the shared experts once.  It
follows the program where the program departs from the source (the
configuration's ``program_departures``): RMSNorm times ``1 + w``, and
rope rotating the two halves of each head's rope lanes, where the source
rotates interleaved pairs (a fixed permutation of ``q_proj``'s and
``kv_a_proj_with_mqa``'s rope columns, which random weights do not
see).

Costs.  Operations and bytes that the algorithm needs, from shapes
alone: latent attention in the absorbed form over each slot's live
tokens, one read of each weight, the held experts' expected share of a
row's top-k (``top_k * held / experts``), matrix products at two
operations per multiply-add.
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

OUT_GAIN = 8.0
EMBED_STD = 0.002
NORM_STD = 0.1
Q_BLOCK = 256
ROW_CHUNK = 2048

# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(conf: dict) -> Dict:
    y = conf["rope_scaling"]
    return {
        "d": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "latent": conf["kv_lora_rank"],
        "nope": conf["qk_nope_head_dim"],
        "rope": conf["qk_rope_head_dim"],
        "v": conf["v_head_dim"],
        "ff": conf["intermediate_size"],
        "expert_ff": conf["moe_intermediate_size"],
        "experts": conf["published"]["n_routed_experts"],
        "held": conf["n_routed_experts"],
        "first_held": conf["deployment"]["first_held_expert"],
        "shared": conf["n_shared_experts"],
        "top_k": conf["num_experts_per_tok"],
        "renormalize": conf["norm_topk_prob"],
        "routed_scale": float(conf["routed_scaling_factor"]),
        "dense_layers": conf["first_k_dense_replace"],
        "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "eps": conf["rms_norm_eps"],
        "rope_theta": float(conf["rope_theta"]),
        "yarn_factor": float(y["factor"]),
        "yarn_original": y["original_max_position_embeddings"],
        "yarn_beta_fast": float(y["beta_fast"]),
        "yarn_beta_slow": float(y["beta_slow"]),
        "yarn_mscale": float(y["mscale"]),
        "yarn_mscale_all_dim": float(y["mscale_all_dim"]),
    }


def program_arch(conf: dict):
    from repro.config import get_arch

    s = sizes(conf)
    arch = dataclasses.replace(get_arch(conf["arch"], smoke=conf.get("smoke", False)),
                               num_layers=s["layers"])
    m, moe, y = arch.mla, arch.moe, arch.rope_scaling
    if m is None or moe is None or y is None or not moe.held_experts:
        raise BenchError(f"the program's {conf['arch']!r} is not a latent-attention "
                         "MoE with an expert share")
    have = {"d": arch.d_model, "heads": arch.num_heads, "latent": m.kv_lora_rank,
            "nope": m.qk_nope_head_dim, "rope": m.qk_rope_head_dim, "v": m.v_head_dim,
            "ff": arch.d_ff, "expert_ff": moe.expert_ff, "experts": moe.num_experts,
            "held": moe.held_experts, "first_held": moe.first_held,
            "shared": moe.shared_experts, "top_k": moe.top_k,
            "renormalize": moe.renormalize, "routed_scale": moe.routed_scale,
            "dense_layers": len(arch.lead), "vocab": arch.vocab_size,
            "layers": arch.num_layers, "eps": arch.norm_eps,
            "rope_theta": arch.rope_theta, "yarn_factor": y.factor,
            "yarn_original": y.original_max_position_embeddings,
            "yarn_beta_fast": y.beta_fast, "yarn_beta_slow": y.beta_slow,
            "yarn_mscale": y.mscale, "yarn_mscale_all_dim": y.mscale_all_dim}
    wrong = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if moe.capacity_factor > 0:
        wrong["capacity_factor"] = (moe.capacity_factor, "dropless")
    if arch.tie_embeddings or arch.logit_softcap or arch.parallel_block:
        wrong["layout"] = "not an untied, plain pre-norm decoder"
    if wrong:
        raise BenchError(f"the program's {conf['arch']!r} differs from "
                         f"{conf['name']}: {wrong} (program, file)")
    return arch


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def attn_leaves(s: Dict) -> List[Tuple[str, tuple, str, int]]:
    d, h, r = s["d"], s["heads"], s["latent"]
    return [
        ("norm_attn", (d,), "norm", 1),
        ("attn.wq", (d, h, s["nope"] + s["rope"]), "in", d),
        ("attn.wkv_a", (d, r + s["rope"]), "in", d),
        ("attn.kv_norm", (r,), "norm", 1),
        ("attn.wk_b", (r, h, s["nope"]), "in", r),
        ("attn.wv_b", (r, h, s["v"]), "in", r),
        ("attn.wo", (h, s["v"], d), "out", h * s["v"]),
        ("norm_ffn", (d,), "norm", 1),
    ]


def dense_leaves(s: Dict) -> List[Tuple[str, tuple, str, int]]:
    d, f = s["d"], s["ff"]
    return attn_leaves(s) + [
        ("mlp.w_gate", (d, f), "in", d),
        ("mlp.w_up", (d, f), "in", d),
        ("mlp.w_down", (f, d), "out", f),
    ]


def moe_leaves(s: Dict) -> List[Tuple[str, tuple, str, int]]:
    """Per-expert shapes for ``moe.w_*`` (stacked over the held experts)."""
    d, f, fs = s["d"], s["expert_ff"], s["shared"] * s["expert_ff"]
    return attn_leaves(s) + [
        ("moe.router", (d, s["experts"]), "in", d),
        ("moe.w_gate", (d, f), "in", d),
        ("moe.w_up", (d, f), "in", d),
        ("moe.w_down", (f, d), "out", f),
        ("moe.shared.w_gate", (d, fs), "in", d),
        ("moe.shared.w_up", (d, fs), "in", d),
        ("moe.shared.w_down", (fs, d), "out", fs),
    ]


GAINS = {"in": 1.0, "out": OUT_GAIN}


def _leaf(key, idx, layer, shape, kind, fan_in):
    x = W.layer_normal(key, idx, layer, shape)
    if kind == "norm":
        return x * NORM_STD
    return x * (GAINS[kind] / math.sqrt(fan_in))


def _held(s: Dict) -> jax.Array:
    return s["first_held"] + jnp.arange(s["held"])


def layer(key: jax.Array, index, s: Dict, dtype, dense: bool) -> Dict[str, jax.Array]:
    """One layer's weights, ``{"attn.wq": ..., ...}``, in ``dtype``; a MoE
    layer's routed experts stacked over the held ones."""
    out = {}
    for i, (name, shape, kind, fan) in enumerate(dense_leaves(s) if dense else moe_leaves(s)):
        if name in ("moe.w_gate", "moe.w_up", "moe.w_down"):
            x = jax.vmap(lambda e: _leaf(jax.random.fold_in(key, e), i, index, shape,
                                         kind, fan))(_held(s))
        else:
            x = _leaf(key, i, index, shape, kind, fan)
        out[name] = x.astype(jnp.float32 if name == "moe.router" else dtype)
    return out


def embedding(key: jax.Array, s: Dict, dtype) -> jax.Array:
    return (W.top_normal(key, 1, (s["vocab"], s["d"])) * EMBED_STD).astype(dtype)


def unembedding(key: jax.Array, s: Dict, dtype) -> jax.Array:
    """``[D, V]``, as the program holds it."""
    return (W.top_normal(key, 3, (s["d"], s["vocab"])) / math.sqrt(s["d"])).astype(dtype)


def final_norm(key: jax.Array, s: Dict, dtype) -> jax.Array:
    return (W.top_normal(key, 2, (s["d"],)) * NORM_STD).astype(dtype)


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for name, x in flat.items():
        *path, leaf = name.split(".")
        node = out
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def program_tree(key: jax.Array, s: Dict, dtype) -> dict:
    """All weights in the program's layout: the leading dense layers
    unrolled, the MoE layers one scanned period stacked over their count,
    under a caller's ``jit``."""
    n_dense = s["dense_layers"]
    lead = [_nest(layer(key, i, s, dtype, dense=True)) for i in range(n_dense)]
    stacked = jax.vmap(lambda i: layer(key, i, s, dtype, dense=False))(
        jnp.arange(n_dense, s["layers"]))
    tree = {"embed": {"tok": embedding(key, s, dtype),
                      "unembed": unembedding(key, s, dtype)},
            "periods": [_nest(stacked)],
            "final_norm": final_norm(key, s, dtype)}
    if lead:
        tree["lead"] = lead
    return tree


def leaf_name(path) -> str:
    """A leaf's name as the reference names it: ``['periods'][0]['attn']
    ['wq']`` and ``['lead'][0]['attn']['wq']`` -> ``attn.wq``."""
    keys = [k.key for k in path if hasattr(k, "key")]
    if keys[0] in ("periods", "lead"):
        return ".".join(keys[1:])
    return keys[-1]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(s: Dict) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding.inv_freq``."""
    dim, base = s["rope"], s["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / s["yarn_factor"]

    def corr_dim(rot):
        return dim * math.log(s["yarn_original"] / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(s["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the base frequency is kept
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope(x, pos, s):
    """x [N, T, H, R], pos [T]: the two halves of the rope lanes rotated
    (the program's departure from the source's interleaved pairs)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_freqs(s))
    m = yarn_mscale(s["yarn_factor"], s["yarn_mscale"]) / yarn_mscale(
        s["yarn_factor"], s["yarn_mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * m)[None, :, None, :], (jnp.sin(ang) * m)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def softmax_scale(s: Dict) -> float:
    return (s["nope"] + s["rope"]) ** -0.5 * yarn_mscale(
        s["yarn_factor"], s["yarn_mscale_all_dim"]) ** 2


def attention(p, h, s, lowp):
    """Latent attention over h [N, T, D], causal from position 0."""
    mm = R.mm
    n, t, _ = h.shape
    r, dn = s["latent"], s["nope"]
    pos = jnp.arange(t)
    q = mm("btd,dhk->bthk", h, p["attn.wq"], lowp)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, s)
    kv = mm("btd,dk->btk", h, p["attn.wkv_a"], lowp)
    c = R.rms_norm(kv[..., :r], p["attn.kv_norm"], s["eps"])
    k_pe = rope(kv[..., None, r:], pos, s)[:, :, 0]
    k_nope = mm("btr,rhn->bthn", c, p["attn.wk_b"], lowp)
    v = mm("btr,rhv->bthv", c, p["attn.wv_b"], lowp)
    scale = softmax_scale(s)
    qb = math.gcd(t, Q_BLOCK)  # the harness pads t to a multiple of 128

    def block(i):
        lo = i * qb
        qn = jax.lax.dynamic_slice_in_dim(q_nope, lo, qb, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, lo, qb, axis=1)
        sc = (mm("bqhn,bshn->bhqs", qn, k_nope, lowp)
              + mm("bqhk,bsk->bhqs", qp, k_pe, lowp)) * scale
        causal = jnp.arange(t)[None, :] <= (lo + jnp.arange(qb))[:, None]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        return mm("bhqs,bshv->bqhv", jax.nn.softmax(sc, axis=-1), v, lowp)

    o = jax.lax.map(block, jnp.arange(t // qb))  # [T/qb, N, qb, H, V]
    o = jnp.moveaxis(o, 0, 1).reshape(n, t, s["heads"], s["v"])
    return mm("bthv,hvd->btd", o, p["attn.wo"], lowp)


def _rows(fn, h):
    """``fn`` over h's rows [M, D] a chunk at a time (bounded memory)."""
    m, d = h.shape
    c = min(ROW_CHUNK, m)
    pad = (-m) % c
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, c, d)
    return jax.lax.map(fn, hp).reshape(-1, d)[:m]


def swiglu(h, w_gate, w_up, w_down, lowp):
    g = R.mm("md,df->mf", h, w_gate, lowp)
    u = R.mm("md,df->mf", h, w_up, lowp)
    return R.mm("mf,fd->md", jax.nn.silu(g) * u, w_down, lowp)


def moe(p, h, s, lowp):
    """The held experts' part of the routed sum, plus the shared experts."""

    def chunk(x):
        probs = jax.nn.softmax(R.mm("md,de->me", x, p["moe.router"], lowp), axis=-1)
        top, idx = jax.lax.top_k(probs, s["top_k"])
        if s["renormalize"]:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        top = top * s["routed_scale"]
        y = swiglu(x, p["moe.shared.w_gate"], p["moe.shared.w_up"],
                   p["moe.shared.w_down"], lowp)
        for j in range(s["held"]):
            gate = jnp.sum(jnp.where(idx == s["first_held"] + j, top, 0.0), axis=-1)
            y = y + gate[:, None] * swiglu(x, p["moe.w_gate"][j], p["moe.w_up"][j],
                                           p["moe.w_down"][j], lowp)
        return y

    return _rows(chunk, h)


def block(p, x, s, dense, lowp=False):
    """One decoder layer over x [N, T, D]."""
    n, t, d = x.shape
    x = x + attention(p, R.rms_norm(x, p["norm_attn"], s["eps"]), s, lowp)
    h = R.rms_norm(x, p["norm_ffn"], s["eps"]).reshape(n * t, d)
    if dense:
        y = _rows(lambda c: swiglu(c, p["mlp.w_gate"], p["mlp.w_up"], p["mlp.w_down"],
                                   lowp), h)
    else:
        y = moe(p, h, s, lowp)
    return x + y.reshape(n, t, d)


@functools.partial(jax.jit, static_argnames=("sk", "dtype", "dense", "lowp"))
def _served_layer(x, key, index, sk, dtype, dense, lowp):
    s = dict(sk)
    p = {n: a.astype(jnp.float32) for n, a in layer(key, index, s, dtype, dense).items()}
    return block(p, x, s, dense, lowp)


def hidden(seed: int, s: Dict, tokens: np.ndarray, dtype, lowp=False):
    """Final-norm hidden states [N, T, D] and the float32 unembedding
    ``[V, D]``, for the weights as served in ``dtype``."""
    key = W.base_key(seed)
    sk = R.sizes_key(s)
    with jax.default_matmul_precision("highest"):
        x = embedding(key, s, dtype).astype(jnp.float32)[jnp.asarray(tokens)]
        for i in range(s["layers"]):
            x = _served_layer(x, key, i, sk, dtype, i < s["dense_layers"], lowp)
        fn = final_norm(key, s, dtype).astype(jnp.float32)
        head = unembedding(key, s, dtype).astype(jnp.float32).T
        return R.rms_norm(x, fn, s["eps"]), head


# ---------------------------------------------------------------------------
# costs
# ---------------------------------------------------------------------------


def attn_params(s: Dict) -> int:
    """Weights a decoded token multiplies in one latent-attention layer,
    in the absorbed form: q, the latent and rope key, W_UK into the
    query, W_UV out of the latent, the output projection."""
    d, h, r = s["d"], s["heads"], s["latent"]
    return (d * h * (s["nope"] + s["rope"]) + d * (r + s["rope"])
            + h * s["nope"] * r + h * r * s["v"] + h * s["v"] * d)


def row_params(s: Dict) -> float:
    """Weights one decoded row multiplies: every layer's attention, the
    dense FFN, the router and shared experts, the held experts' expected
    share of the row's top-k (``top_k * held / experts`` of them), and the
    unembedding."""
    d, fe = s["d"], s["expert_ff"]
    n_moe = s["layers"] - s["dense_layers"]
    held_share = s["top_k"] * s["held"] / s["experts"]
    moe_layer = d * s["experts"] + 3 * d * fe * (s["shared"] + held_share)
    return (s["layers"] * attn_params(s) + s["dense_layers"] * 3 * d * s["ff"]
            + n_moe * moe_layer + d * s["vocab"])


def row_width(s: Dict) -> int:
    """Lanes of one cached latent row: the latent and the rope key."""
    return s["latent"] + s["rope"]


def latent_flops(s: Dict, kv_tokens: int) -> float:
    """One layer's absorbed decode attention over ``kv_tokens`` live
    tokens summed over the rows: every head scores the whole row and sums
    the latent (2 * H * (576 + 512) a token at the published widths)."""
    return 2.0 * s["heads"] * (row_width(s) + s["latent"]) * kv_tokens


def latent_bytes(s: Dict, kv_tokens: int, rows: int, itemsize: int) -> float:
    """One layer's latent decode kernel: each live token's row read once
    (whatever the pool pads it to), each row's queries read and latent
    outputs written once."""
    return (kv_tokens * row_width(s)
            + rows * s["heads"] * (row_width(s) + s["latent"])) * itemsize


def decode_flops(s: Dict, rows: int, kv_tokens: int) -> float:
    return 2.0 * row_params(s) * rows + s["layers"] * latent_flops(s, kv_tokens)


def kernel_counters(s: Dict, rows: int, kv_tokens: int) -> dict:
    """The paged latent decode kernel's work over every layer, read by
    ``paged_latent_decode_roofline``: rows in bf16, as served."""
    return {
        "mla_flops": latent_flops(s, kv_tokens) * s["layers"],
        "mla_bytes": latent_bytes(s, kv_tokens, rows, 2) * s["layers"],
    }
