"""Plain float32 reference of MiniCPM-2B as this repository runs it.

Written from the equations, in ``jax.numpy``, with every matrix product
at ``Precision.HIGHEST``; it imports nothing of the program.  Weights
come from ``weights.py`` by the seed, one layer at a time, so the
reference never holds more than one layer beside the embedding.

The equations are the program's, which depart from the published
MiniCPM-2B in four places (each listed in the configuration files under
``program_departures``): the input embedding is scaled by
``sqrt(d_model)`` (published: ``scale_emb`` 12), the logits are not
divided by ``d_model / dim_model_base``, RMSNorm multiplies by
``1 + w`` and uses eps 1e-6 (published: ``w``, 1e-5).

``lowp=True`` is the control: every matrix product's operands are first
rounded to float8 (e4m3, one scale per tensor), the step below the
bfloat16 that the configurations compute in.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient
    passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(spec, a, b, lowp=False):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


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


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("sk", "dtype", "lowp"))
def _served_layer(x, key, index, sk, dtype, lowp):
    s = dict(sk)
    p = {n: a.astype(jnp.float32) for n, a in W.layer(key, index, s, dtype).items()}
    return block(p, x, s, lowp)


def hidden(seed: int, s: Dict, tokens: np.ndarray, dtype, lowp=False):
    """Final-norm hidden states [N, T, D] and the float32 unembedding
    table, for the weights as served in ``dtype``."""
    key = W.base_key(seed)
    with jax.default_matmul_precision("highest"):
        tok = W.embedding(key, s, dtype).astype(jnp.float32)
        x = embed(tok, jnp.asarray(tokens), s)
        for i in range(s["layers"]):
            x = _served_layer(x, key, i, _sizes_key(s), dtype, lowp)
        fn = W.final_norm(key, s, dtype).astype(jnp.float32)
        return rms_norm(x, fn, s["eps"]), tok


@functools.partial(jax.jit, static_argnames=("lowp",))
def _logits(h, tok, lowp=False):
    return mm("md,vd->mv", h, tok, lowp)


def served_gaps(seed: int, s: Dict, seqs: Sequence[Sequence[int]],
                prompt_lens: Sequence[int], dtype, control=False,
                chunk=256) -> List[float]:
    """For each sequence (prompt then served tokens), the widest gap by
    which a served token's reference logit lies below the reference's
    best, over every served position, in units of the standard deviation
    of the reference's logits over the vocabulary at that position (so
    that the number reads alike at every width).

    ``control=True`` reads the control instead: at each of the same
    positions, the token that the float8 reference puts first, and its
    gap in the float32 reference."""
    t = max(len(q) for q in seqs)
    t = -(-t // 128) * 128
    tokens = np.zeros((len(seqs), t), np.int32)
    rows, targets, owner = [], [], []
    for r, (q, plen) in enumerate(zip(seqs, prompt_lens)):
        tokens[r, :len(q)] = q
        for p in range(plen - 1, len(q) - 1):
            rows.append((r, p))
            targets.append(q[p + 1])
            owner.append(r)
    h, tok = hidden(seed, s, tokens, dtype)
    if control:
        h8, _ = hidden(seed, s, tokens, dtype, lowp=True)
    idx = np.asarray(rows)
    gaps = np.zeros(len(rows))
    for c in range(0, len(rows), chunk):
        sel = idx[c:c + chunk]
        hr = h[sel[:, 0], sel[:, 1]]
        lg = np.asarray(_logits(hr, tok))
        if control:
            pick = np.asarray(_logits(h8[sel[:, 0], sel[:, 1]], tok, lowp=True)
                              ).argmax(-1)
        else:
            pick = np.asarray(targets[c:c + chunk])
        gaps[c:c + chunk] = (lg.max(-1) - lg[np.arange(len(sel)), pick]) / lg.std(-1)
    out = [0.0] * len(seqs)
    for r, g in zip(owner, gaps):
        out[r] = max(out[r], float(g))
    return out


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW, three steps
# ---------------------------------------------------------------------------


def train_params(seed: int, s: Dict) -> Dict:
    key = W.base_key(seed)
    return {"tok": W.embedding(key, s, jnp.float32),
            "final_norm": W.final_norm(key, s, jnp.float32),
            "layers": [W.layer(key, i, s, jnp.float32) for i in range(s["layers"])]}


def _nll_sum(params, tokens, labels, sk, lowp):
    s = dict(sk)
    x = embed(params["tok"], tokens, s)
    for p in params["layers"]:
        x = block(p, x, s, lowp)
    x = rms_norm(x, params["final_norm"], s["eps"])
    logits = mm("ntd,vd->ntv", x, params["tok"], lowp)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


_grad_row = jax.jit(jax.value_and_grad(_nll_sum), static_argnames=("sk", "lowp"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _acc(a, b):
    return jax.tree.map(jnp.add, a, b)


def lr_at(t: Dict, step: int) -> float:
    """The WSD schedule: linear warm-up, a plateau, a linear decay to 10%."""
    warm = min(step / max(t["warmup_steps"], 1), 1.0)
    end = t["warmup_steps"] + t["stable_steps"]
    frac = 1.0 - 0.9 * min(max((step - end) / max(t["decay_steps"], 1), 0.0), 1.0)
    return t["learning_rate"] * warm * frac


@functools.partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("b1", "b2", "eps", "wd"))
def _adamw(params, m, v, g, lr, bc1, bc2, b1, b2, eps, wd):
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps) + wd * p),
        params, m, v)
    return params, m, v


def train_steps(seed: int, s: Dict, t: Dict, batches: Sequence[np.ndarray],
                lowp=False, half=False) -> Dict:
    """Runs AdamW over ``batches`` ([B, T+1] rows each) from the seeded
    weights.  Returns each step's loss, the first step's gradient norms
    by leaf (after clipping, as the optimizer takes it) and the norms of
    the parameters' change after the last step."""
    sk = _sizes_key(s)
    with jax.default_matmul_precision("highest"):
        params = train_params(seed, s)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for step, rows in enumerate(batches, 1):
            rows = np.asarray(rows)
            if half:
                rows = rows[: len(rows) // 2]
            total, g, loss = rows.shape[0] * (rows.shape[1] - 1), None, 0.0
            for i in range(rows.shape[0]):
                li, gi = _grad_row(params, jnp.asarray(rows[i:i + 1, :-1]),
                                   jnp.asarray(rows[i:i + 1, 1:]), sk, lowp)
                loss += float(li)
                g = gi if g is None else _acc(g, gi)
                del gi
            g = jax.tree.map(lambda x: x / total, g)
            gnorm = math.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(g)))
            clip = min(1.0, t["grad_clip_norm"] / max(gnorm, 1e-9))
            g = jax.tree.map(lambda x: x * clip, g)
            if step == 1:
                first = {k: float(v) for k, v in W.leaf_norms(g).items()}
            losses.append(loss / total)
            params, m, v = _adamw(
                params, m, v, g, lr_at(t, step), 1.0 - t["beta1"] ** step,
                1.0 - t["beta2"] ** step, b1=t["beta1"], b2=t["beta2"],
                eps=t["eps"], wd=t["weight_decay"])
            del g
        del m, v
        start = train_params(seed, s)
        delta = {k: float(v) for k, v in W.leaf_norms(
            jax.tree.map(jnp.subtract, params, start)).items()}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
