"""What the plain references of every architecture share.

The matrix product at ``Precision.HIGHEST`` with its float8 control, the
program's RMSNorm, the comparison of served tokens with an
architecture's reference (``served_gaps``), and AdamW with the WSD
schedule over an architecture's loss (``train_steps``).  The equations of
each model are in ``bench/archs/<arch>.py``; none of it imports the
program.

``lowp=True`` is the control: every matrix product's operands are first
rounded to float8 (e4m3, one scale per tensor), the step below the
bfloat16 that the configurations compute in.
"""

from __future__ import annotations

import functools
import math
from types import ModuleType
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


def sizes_key(s: Dict):
    """``s`` as a static argument of ``jit``."""
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("lowp",))
def _logits(h, tok, lowp=False):
    return mm("md,vd->mv", h, tok, lowp)


def served_gaps(arch: ModuleType, seed: int, s: Dict, seqs: Sequence[Sequence[int]],
                prompt_lens: Sequence[int], dtype, control=False,
                chunk=256) -> List[float]:
    """For each sequence (prompt then served tokens), the widest gap by
    which a served token's logit in ``arch``'s reference lies below the reference's
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
    h, tok = arch.hidden(seed, s, tokens, dtype)
    if control:
        h8, _ = arch.hidden(seed, s, tokens, dtype, lowp=True)
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


@functools.lru_cache(maxsize=None)
def _grad_row(nll_sum):
    return jax.jit(jax.value_and_grad(nll_sum), static_argnames=("sk", "lowp"))


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


def train_steps(arch: ModuleType, seed: int, s: Dict, t: Dict,
                batches: Sequence[np.ndarray], lowp=False, half=False) -> Dict:
    """Runs AdamW over ``batches`` ([B, T+1] rows each) on ``arch``'s
    loss from the seeded weights.  Returns each step's loss, the first
    step's gradient norms by leaf (after clipping, as the optimizer takes
    it) and the norms of the parameters' change after the last step."""
    sk = sizes_key(s)
    grad_row = _grad_row(arch.nll_sum)
    with jax.default_matmul_precision("highest"):
        params = arch.train_params(seed, s)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for step, rows in enumerate(batches, 1):
            rows = np.asarray(rows)
            if half:
                rows = rows[: len(rows) // 2]
            total, g, loss = rows.shape[0] * (rows.shape[1] - 1), None, 0.0
            for i in range(rows.shape[0]):
                li, gi = grad_row(params, jnp.asarray(rows[i:i + 1, :-1]),
                                  jnp.asarray(rows[i:i + 1, 1:]), sk, lowp)
                loss += float(li)
                g = gi if g is None else _acc(g, gi)
                del gi
            g = jax.tree.map(lambda x: x / total, g)
            gnorm = math.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(g)))
            clip = min(1.0, t["grad_clip_norm"] / max(gnorm, 1e-9))
            g = jax.tree.map(lambda x: x * clip, g)
            if step == 1:
                first = {k: float(v) for k, v in W.leaf_norms(g, arch.leaf_name).items()}
            losses.append(loss / total)
            params, m, v = _adamw(
                params, m, v, g, lr_at(t, step), 1.0 - t["beta1"] ** step,
                1.0 - t["beta2"] ** step, b1=t["beta1"], b2=t["beta2"],
                eps=t["eps"], wd=t["weight_decay"])
            del g
        del m, v
        start = arch.train_params(seed, s)
        delta = {k: float(v) for k, v in W.leaf_norms(
            jax.tree.map(jnp.subtract, params, start), arch.leaf_name).items()}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
