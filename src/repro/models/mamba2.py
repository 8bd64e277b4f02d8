"""Mamba-2 SSD (state-space duality) block — chunked formulation.

Training/prefill use the chunked algorithm (Dao & Gu 2024): quadratic
attention-like compute inside chunks of length Q, linear state passing
between chunks.  Decode is a single O(1) state update per token — the
reason the ssm/hybrid archs run the long_500k cell.

The per-chunk compute (the hot spot) has a Pallas kernel in
``repro.kernels.ssd_scan``; this module is the pure-jnp path used for the
dry-run and as the kernel's structural reference.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ArchConfig, MambaConfig
from repro.distributed.sharding import shard
from repro.models.layers import dense_init

Params = Dict[str, Any]


def _dims(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    m = cfg.mamba
    assert m is not None
    d_in = m.expand * cfg.d_model
    nheads = d_in // m.head_dim
    return d_in, nheads, m.head_dim, m.d_state


def init_mamba(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    m = cfg.mamba
    d = cfg.d_model
    d_in, h, p, n = _dims(cfg)
    ks = jax.random.split(rng, 6)
    d_xbc = d_in + 2 * n  # conv runs over concat(x, B, C)
    return {
        # fused input projection -> [z, x, B, C, dt]
        "in_proj": dense_init(ks[0], (d, d_in + d_xbc + h), dtype, d),
        "conv_w": dense_init(ks[1], (m.d_conv, d_xbc), dtype, m.d_conv),
        "conv_b": jnp.zeros((d_xbc,), dtype=dtype),
        "dt_bias": jnp.zeros((h,), dtype=jnp.float32),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)
        ),  # A = -exp(A_log)
        "D": jnp.ones((h,), dtype=jnp.float32),
        "norm_w": jnp.zeros((d_in,), dtype=dtype),
        "out_proj": dense_init(ks[2], (d_in, d), dtype, d_in),
    }


def _split_proj(cfg: ArchConfig, proj: jax.Array):
    d_in, h, p, n = _dims(cfg)
    z, xbc, dt = jnp.split(proj, [d_in, d_in + d_in + 2 * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv over time. xbc: [B,T,C], w: [K,C].

    Returns (out [B,T,C], new_state [B,K-1,C])."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), dtype=xbc.dtype)
    else:
        pad = state
    full = jnp.concatenate([pad, xbc], axis=1)  # [B, T+K-1, C]
    out = jnp.zeros_like(xbc)
    for i in range(k):  # K is tiny (4): unrolled taps
        out = out + full[:, i : i + xbc.shape[1], :] * w[i][None, None, :]
    out = out + b[None, None, :]
    new_state = full[:, full.shape[1] - (k - 1) :, :]
    return jax.nn.silu(out), new_state


def ssd_chunked_ref(
    x: jax.Array,  # [B, T, H, P] (dt-scaled inputs)
    a: jax.Array,  # [B, T, H] decay in (0,1)
    B: jax.Array,  # [B, T, N]
    C: jax.Array,  # [B, T, N]
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # [B, H, N, P]
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y [B,T,H,P], final_state [B,H,N,P])."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    q = chunk
    assert t % q == 0, f"T={t} not divisible by chunk={q}"
    nc = t // q

    xc = x.reshape(b, nc, q, h, p)
    ac = a.reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, n)
    Cc = C.reshape(b, nc, q, n)

    log_a = jnp.log(jnp.clip(ac.astype(jnp.float32), 1e-20))
    cum = jnp.cumsum(log_a, axis=2)  # [b,nc,q,h] inclusive cumsum

    # --- intra-chunk (the "attention-like" quadratic part) ---------------
    # L[s->t] = exp(cum_t - cum_s) for s <= t  (decay between s and t).
    # Mask BEFORE exp: above-diagonal rel is positive and can overflow to
    # inf, which would poison gradients through the where (inf * 0 = nan).
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [b,nc,q,q,h]
    tri = jnp.tril(jnp.ones((q, q), dtype=bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, rel, -jnp.inf))
    cb = jnp.einsum(
        "bcqn,bcsn->bcqs", Cc.astype(jnp.float32), Bc.astype(jnp.float32)
    )  # [b,nc,q,q]
    att = cb[:, :, :, :, None] * decay  # [b,nc,q,s,h]
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", att, xc.astype(jnp.float32))

    # --- chunk states ------------------------------------------------------
    # state contribution of step s within its chunk: decay to chunk end
    end_decay = jnp.exp(cum[:, :, -1:, :] - cum)  # [b,nc,q,h]
    states = jnp.einsum(
        "bcsn,bcsh,bcshp->bchnp",
        Bc.astype(jnp.float32),
        end_decay,
        xc.astype(jnp.float32),
    )  # [b,nc,h,n,p]

    # --- inter-chunk recurrence over chunk states -------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [b,nc,h] total decay of chunk

    def step(carry, inp):
        s_prev = carry  # [b,h,n,p]
        s_chunk, d_chunk = inp  # [b,h,n,p], [b,h]
        s_new = s_chunk + d_chunk[:, :, None, None] * s_prev
        return s_new, s_prev  # emit state *entering* the chunk

    init = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, n, p), dtype=jnp.float32)
    )
    states_t = jnp.moveaxis(states, 1, 0)  # [nc,b,h,n,p]
    decay_t = jnp.moveaxis(chunk_decay, 1, 0)  # [nc,b,h]
    final, entering = jax.lax.scan(step, init, (states_t, decay_t))
    entering = jnp.moveaxis(entering, 0, 1)  # [b,nc,h,n,p]

    # --- inter-chunk output: y_inter[t] = C_t . (decay_to_t * S_entering) --
    in_decay = jnp.exp(cum)  # decay from chunk start to t (inclusive)
    y_inter = jnp.einsum(
        "bcqn,bcqh,bchnp->bcqhp", Cc.astype(jnp.float32), in_decay, entering
    )

    y = (y_intra + y_inter).reshape(b, t, h, p)
    return y, final


def ssd_sequential_ref(
    x: jax.Array, a: jax.Array, B: jax.Array, C: jax.Array,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """O(T) sequential oracle (slow, exact) for property tests."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    state = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, n, p), dtype=jnp.float32)
    )

    def step(s, inp):
        xt, at, Bt, Ct = inp  # [b,h,p],[b,h],[b,n],[b,n]
        s = s * at[:, :, None, None] + jnp.einsum("bn,bhp->bhnp", Bt, xt)
        y = jnp.einsum("bn,bhnp->bhp", Ct, s)
        return s, y

    xs = (
        jnp.moveaxis(x.astype(jnp.float32), 1, 0),
        jnp.moveaxis(a.astype(jnp.float32), 1, 0),
        jnp.moveaxis(B.astype(jnp.float32), 1, 0),
        jnp.moveaxis(C.astype(jnp.float32), 1, 0),
    )
    final, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def mamba_block(
    params: Params,
    u: jax.Array,  # [B, T, D]
    cfg: ArchConfig,
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Full Mamba-2 block. cache = {"conv": [B,K-1,C], "ssm": [B,H,N,P]}."""
    m = cfg.mamba
    assert m is not None
    d_in, h, p, n = _dims(cfg)
    bsz, t, _ = u.shape

    cdt = u.dtype  # weights meet activations in the compute dtype
    proj = jnp.einsum("btd,de->bte", u, params["in_proj"].astype(cdt))
    z, xbc, dt = _split_proj(cfg, proj)
    conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv(
        xbc, params["conv_w"].astype(cdt), params["conv_b"].astype(cdt),
        conv_state,
    )
    x, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    x = x.reshape(bsz, t, h, p)
    x = shard(x, "batch", "seq_inner", "mamba_heads", None)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B,T,H]
    A = -jnp.exp(params["A_log"])  # [H]
    a = jnp.exp(dt * A[None, None, :])  # decay in (0,1)
    x_dt = x.astype(jnp.float32) * dt[..., None]

    ssm_state = cache["ssm"] if cache is not None else None
    if t == 1 and cache is not None:
        # decode: one fused state update
        state = ssm_state.astype(jnp.float32)
        state = state * a[:, 0, :, None, None] + jnp.einsum(
            "bn,bhp->bhnp", B[:, 0].astype(jnp.float32), x_dt[:, 0]
        )
        y = jnp.einsum("bn,bhnp->bhp", C[:, 0].astype(jnp.float32), state)[
            :, None
        ]  # [B,1,H,P]
        final_state = state
    else:
        # Pad T to a multiple of the chunk: x=0 contributes nothing to the
        # state, a=1 leaves the decay untouched, so padded steps are inert
        # and the final state stays exact.
        pad = (-t) % m.chunk_size
        x_c, a_c, B_c, C_c = x_dt, a, B, C
        if pad:
            x_c = jnp.pad(x_dt, ((0, 0), (0, pad), (0, 0), (0, 0)))
            a_c = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
            B_c = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
            C_c = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        # The jnp path on every backend: kernels/ssd_scan does not lower
        # for TPU yet (ROADMAP B2).
        y, final_state = ssd_chunked_ref(
            x_c, a_c, B_c, C_c, m.chunk_size, ssm_state
        )
        if pad:
            y = y[:, :t]

    y = y + params["D"][None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(bsz, t, d_in).astype(u.dtype)
    # gated RMSNorm (mamba2 style): norm(y * silu(z))
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)).astype(u.dtype)
    y = y * (1.0 + params["norm_w"].astype(u.dtype))
    out = jnp.einsum("bte,ed->btd", y, params["out_proj"].astype(cdt))

    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "ssm": final_state.astype(cache["ssm"].dtype)}
    return shard(out, "batch", "seq_inner", "embed"), new_cache


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype) -> Params:
    m = cfg.mamba
    d_in, h, p, n = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, m.d_conv - 1, d_in + 2 * n), dtype=dtype),
        "ssm": jnp.zeros((batch, h, n, p), dtype=jnp.float32),
    }
