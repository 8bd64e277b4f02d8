"""Mixture-of-Experts FFN with capacity-based dispatch (Mixtral / Switch
style), expert-parallel over the "expert" logical axis.

Dispatch/combine are dense einsums over one-hot routing tensors — under
GSPMD with experts sharded over the model axis this lowers to the
canonical all-to-all pattern.  The router *is* the paper's
message-distribution scheduler at silicon scale: tokens are messages,
experts are tasks, capacity overflow is mailbox backpressure (dropped
tokens = load imbalance loss), and the auxiliary balance loss plays the
role of JSQ pressure.  See DESIGN.md §5.
"""

from __future__ import annotations

import contextvars
from contextlib import contextmanager
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ArchConfig, MoEConfig
from repro.distributed.sharding import shard
from repro.models.layers import dense_init, init_mlp, mlp

Params = Dict[str, Any]

# Dispatch implementation selector ("einsum" = paper-era dense one-hot
# dispatch, the baseline; "scatter" = sort/scatter dispatch, the §Perf
# optimization). Context-scoped so the dry-run can sweep it per cell.
_impl = contextvars.ContextVar("moe_impl", default="einsum")


@contextmanager
def moe_implementation(name: str):
    if name not in ("einsum", "scatter"):
        raise ValueError(f"unknown moe impl {name!r}")
    token = _impl.set(name)
    try:
        yield
    finally:
        _impl.reset(token)


def moe_apply(params, x, moe, rng=None):
    if _impl.get() == "scatter":
        return moe_ffn_scatter(params, x, moe, rng)
    return moe_ffn(params, x, moe, rng)


def init_moe(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    """The router over every expert, and the weights of the experts this
    chip holds (all of them unless ``held_experts`` names a share), plus
    the shared experts as one SwiGLU where there are any."""
    moe = cfg.moe
    assert moe is not None
    d, ff = cfg.d_model, cfg.expert_ff
    e = moe.held_experts or moe.num_experts
    ks = jax.random.split(rng, 4)
    p = {
        "router": dense_init(ks[0], (d, moe.num_experts), jnp.float32, d),
        "w_gate": dense_init(ks[1], (e, d, ff), dtype, d),
        "w_up": dense_init(ks[2], (e, d, ff), dtype, d),
        "w_down": dense_init(ks[3], (e, ff, d), dtype, ff),
    }
    if moe.shared_experts:
        p["shared"] = init_mlp(jax.random.fold_in(rng, 4), cfg, dtype,
                               ff=moe.shared_experts * ff)
    return p


def moe_share(
    params: Params,
    x: jax.Array,  # [B, T, D]
    moe: MoEConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One chip's share of a DeepSeek-MoE layer (expert parallelism
    without the exchange).

    The router scores all ``num_experts`` in float32 and picks the top-k
    greedily; the weights are renormalised only where the config says so,
    then scaled by ``routed_scale``.  The chip computes the part of the
    routed sum that its held experts give, dropless: every held expert
    runs over every token, weighted by that token's gate for it (zero
    where it was routed elsewhere), which is exact and, at decode, reads
    each held expert's weights once, as a grouped kernel would.  The
    shared experts are added once.  Returns (output [B, T, D], the
    Switch-style aux loss, the (token, held expert) assignments per batch
    row [B])."""
    b, t, d = x.shape
    e, k, held = moe.num_experts, moe.top_k, moe.held_experts
    xf = x.reshape(b * t, d)
    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [n, k]
    if moe.renormalize:
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
    gate_vals = gate_vals * moe.routed_scale
    local = gate_idx - moe.first_held
    mine = (local >= 0) & (local < held)
    # [n, held]: each token's gate for each held expert, 0 where unrouted
    combine = jnp.sum(
        jax.nn.one_hot(jnp.where(mine, local, held), held + 1,
                       dtype=jnp.float32)[..., :held] * gate_vals[..., None],
        axis=1,
    )
    dt = x.dtype
    gate = jnp.einsum("nd,edf->enf", xf, params["w_gate"].astype(dt))
    up = jnp.einsum("nd,edf->enf", xf, params["w_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("enf,efd->end", h, params["w_down"].astype(dt))
    y = jnp.einsum("end,ne->nd", out, combine.astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    y = y.reshape(b, t, d)
    if "shared" in params:
        y = y + mlp(params["shared"], x)

    me = jnp.mean(probs, axis=0)
    frac = jnp.sum(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=(0, 1)
    ) / max(b * t * k, 1)
    aux = moe.aux_loss_weight * e * jnp.sum(frac * me)
    rows = jnp.sum(mine.reshape(b, t * k), axis=-1).astype(jnp.int32)
    return y, aux.astype(jnp.float32), rows


def _fcfs_positions(gate_idx: jax.Array, e: int) -> jax.Array:
    """Rank-major FCFS capacity positions [n, k] — the single contract
    shared by the einsum path, the scatter path, and the moe_gating
    kernel (primary choices claim capacity before secondary ones)."""
    n, k = gate_idx.shape
    counts = jnp.zeros((e,), dtype=jnp.int32)
    pos_cols = []
    for kk in range(k):
        onehot = jax.nn.one_hot(gate_idx[:, kk], e, dtype=jnp.int32)
        within = jnp.cumsum(onehot, axis=0) - onehot
        pos_cols.append(jnp.sum((counts[None, :] + within) * onehot, axis=-1))
        counts = counts + jnp.sum(onehot, axis=0)
    return jnp.stack(pos_cols, axis=1)


def _capacity(tokens: int, moe: MoEConfig) -> int:
    if moe.capacity_factor <= 0:
        # Dropless: worst case routes every choice to one expert. Used by
        # smoke configs (exactness) and decode (a dropped token in serving
        # is a corrupted response, not a soft loss-regression).
        return tokens * moe.top_k
    cap = int(tokens * moe.top_k * moe.capacity_factor / moe.num_experts)
    return max(cap, 1)


def moe_ffn_scatter(
    params: Params,
    x: jax.Array,  # [B, T, D]
    moe: MoEConfig,
    rng: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter/gather MoE dispatch — O(n*k*d) data movement.

    The one-hot einsum formulation (``moe_ffn``) materializes dispatch
    work proportional to n*e*cap*d, which at train_4k scale (n~1M
    tokens) dwarfs the expert FLOPs themselves (the §Perf mixtral
    baseline measured ~20x the useful compute). Here tokens are placed
    into expert buffers by *indexed scatter* and combined back by
    *indexed gather*:

      buffer[expert, pos] = x[token]        (scatter-set, keep mask)
      y[token] += gate * out[expert, pos]   (gather)

    using the same rank-major FCFS capacity contract as the moe_gating
    kernel (which computes idx/pos/keep fused on TPU). Under EP sharding
    the scatter/gather lower to the same all-to-all pattern, minus the
    one-hot matmuls.
    """
    b, t, d = x.shape
    e, k = moe.num_experts, moe.top_k
    n = b * t
    xf = x.reshape(n, d)

    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [n, k]
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    cap = _capacity(n, moe)
    pos = _fcfs_positions(gate_idx, e)  # [n, k]
    keep = pos < cap

    # scatter tokens into expert buffers [e*cap, d]
    flat_slot = jnp.where(keep, gate_idx * cap + pos, e * cap)  # dropped -> OOB
    buffers = jnp.zeros((e * cap + 1, d), dtype=xf.dtype)
    tok_rep = jnp.repeat(jnp.arange(n), k).reshape(n, k)
    buffers = buffers.at[flat_slot.reshape(-1)].set(
        xf[tok_rep.reshape(-1)], mode="drop"
    )
    expert_in = buffers[: e * cap].reshape(e, cap, d)
    expert_in = shard(expert_in, "expert", "capacity", "embed")

    gate = jnp.einsum(
        "ecd,edf->ecf", expert_in, params["w_gate"].astype(expert_in.dtype)
    )
    up = jnp.einsum(
        "ecd,edf->ecf", expert_in, params["w_up"].astype(expert_in.dtype)
    )
    h = jax.nn.silu(gate) * up
    h = shard(h, "expert", "capacity", "expert_ffn")
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(h.dtype))
    expert_out = shard(expert_out, "expert", "capacity", "embed")

    # gather back and combine
    flat_out = expert_out.reshape(e * cap, d)
    safe_slot = jnp.minimum(flat_slot, e * cap - 1)
    picked = flat_out[safe_slot.reshape(-1)].reshape(n, k, d)
    w = (gate_vals * keep.astype(jnp.float32)).astype(picked.dtype)
    y = jnp.einsum("nkd,nk->nd", picked, w)

    me = jnp.mean(probs, axis=0)
    frac = jnp.sum(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=(0, 1)
    ) / max(n * k, 1)
    aux = moe.aux_loss_weight * e * jnp.sum(frac * me)
    return y.reshape(b, t, d).astype(x.dtype), aux.astype(jnp.float32)


def moe_ffn(
    params: Params,
    x: jax.Array,  # [B, T, D]
    moe: MoEConfig,
    rng: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [B,T,D], aux load-balance loss scalar)."""
    b, t, d = x.shape
    e, k = moe.num_experts, moe.top_k
    n = b * t
    xf = x.reshape(n, d)

    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    if moe.router_jitter > 0 and rng is not None:
        logits = logits + moe.router_jitter * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)  # [n, e]

    # top-k gating with renormalized weights
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [n, k]
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    cap = _capacity(n, moe)
    pos = _fcfs_positions(gate_idx, e)  # [n, k]
    keep = pos < cap  # capacity overflow -> token choice dropped

    # dispatch tensor [n, e, cap]
    disp = (
        jax.nn.one_hot(gate_idx, e, dtype=xf.dtype)[:, :, :, None]
        * jax.nn.one_hot(pos, cap, dtype=xf.dtype)[:, :, None, :]
        * keep[:, :, None, None].astype(xf.dtype)
    ).sum(axis=1)  # [n, e, cap]
    combine = (
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)[:, :, :, None]
        * jax.nn.one_hot(pos, cap, dtype=jnp.float32)[:, :, None, :]
        * (keep.astype(jnp.float32) * gate_vals)[:, :, None, None]
    ).sum(axis=1)  # [n, e, cap]

    # all-to-all happens here under EP sharding
    expert_in = jnp.einsum("nec,nd->ecd", disp, xf)
    expert_in = shard(expert_in, "expert", "capacity", "embed")
    gate = jnp.einsum(
        "ecd,edf->ecf", expert_in, params["w_gate"].astype(expert_in.dtype)
    )
    up = jnp.einsum(
        "ecd,edf->ecf", expert_in, params["w_up"].astype(expert_in.dtype)
    )
    h = jax.nn.silu(gate) * up
    h = shard(h, "expert", "capacity", "expert_ffn")
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(h.dtype))
    expert_out = shard(expert_out, "expert", "capacity", "embed")

    y = jnp.einsum("nec,ecd->nd", combine.astype(expert_out.dtype), expert_out)

    # Switch-style auxiliary load-balance loss.
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    frac = jnp.sum(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=(0, 1)
    ) / max(n * k, 1)
    aux = moe.aux_loss_weight * e * jnp.sum(frac * me)

    return y.reshape(b, t, d).astype(x.dtype), aux.astype(jnp.float32)
