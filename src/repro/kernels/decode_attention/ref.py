"""Oracle for single-token GQA decode attention over a KV cache."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def decode_attention_ref(
    q: jax.Array,       # [B, H, D] one query token per sequence
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,  # [B, S, Hkv, D]
    kv_len: jax.Array,   # [B] valid prefix lengths
    window: int = 0,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(s)[None, :]
    mask = pos < kv_len[:, None]
    if window > 0:
        mask = mask & (pos > kv_len[:, None] - 1 - window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    # kv_len == 0 (fresh slot): no valid position exists, so the output
    # is zero by convention — matching the kernel, whose running softmax
    # never accumulates anything.  A bare softmax over an all-masked row
    # would instead return a uniform mixture of garbage.
    any_valid = mask.any(axis=-1)[:, None, None, None]
    out = jnp.where(any_valid, out, 0.0)
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged reference path: gather pages to a dense cache, reuse the oracle
# ---------------------------------------------------------------------------


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize the dense per-sequence cache a page table describes.

    pages [P, page, Hkv*D] + table [B, n] -> [B, n*page, Hkv*D].  This
    is the *reference* semantics of the paged kernel's DMA gather — the
    kernel never builds this array."""
    b, n = page_table.shape
    page = pages.shape[1]
    dense = pages[page_table]  # [B, n, page, Hkv*D]
    return dense.reshape(b, n * page, pages.shape[2])


def paged_decode_attention_ref(
    q: jax.Array,           # [B, H, D]
    k_pages: jax.Array,     # [P, page, Hkv*D]
    v_pages: jax.Array,     # [P, page, Hkv*D]
    page_table: jax.Array,  # [B, n] int32
    kv_len: jax.Array,      # [B]
    window: int = 0,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    b, _, d = q.shape
    s = page_table.shape[1] * k_pages.shape[1]
    k_dense = gather_pages(k_pages, page_table).reshape(b, s, -1, d)
    v_dense = gather_pages(v_pages, page_table).reshape(b, s, -1, d)
    return decode_attention_ref(
        q, k_dense, v_dense, kv_len, window=window, sm_scale=sm_scale
    )


def paged_kv_append_ref(
    k_new: jax.Array,       # [B, Hkv, D]
    v_new: jax.Array,       # [B, Hkv, D]
    k_pages: jax.Array,     # [P, page, Hkv*D]
    v_pages: jax.Array,     # [P, page, Hkv*D]
    page_table: jax.Array,  # [B, n] int32
    pos: jax.Array,         # [B] write positions
) -> "tuple[jax.Array, jax.Array]":
    """Scatter semantics of the in-place append kernel (functional)."""
    page = k_pages.shape[1]
    b = k_new.shape[0]
    rows = jnp.arange(b)
    target_page = page_table[rows, pos // page]  # [B]
    offset = pos % page
    return (
        k_pages.at[target_page, offset].set(k_new.reshape(b, -1)),
        v_pages.at[target_page, offset].set(v_new.reshape(b, -1)),
    )


def paged_latent_decode_ref(
    q: jax.Array,           # [B, H, W] latent-space queries
    pages: jax.Array,       # [P, page, W] latent rows
    page_table: jax.Array,  # [B, n] int32
    kv_len: jax.Array,      # [B]
    value_dim: int,
    sm_scale: float,
) -> jax.Array:
    """Every head's query scored against each live row's W lanes, the
    output the softmax-weighted sum of the rows' first ``value_dim``
    lanes: ``[B, H, value_dim]``, in float32 arithmetic."""
    rows = gather_pages(pages, page_table).astype(jnp.float32)  # [B, S, W]
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows,
                   precision=jax.lax.Precision.HIGHEST) * sm_scale
    live = (jnp.arange(rows.shape[1])[None, :] < kv_len[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", p, rows[..., :value_dim],
                     precision=jax.lax.Precision.HIGHEST)
    out = jnp.where(live.any(axis=-1)[..., None], out, 0.0)
    return out.astype(q.dtype)


def paged_latent_append_ref(
    row: jax.Array,         # [B, W]
    pages: jax.Array,       # [P, page, W]
    page_table: jax.Array,  # [B, n] int32
    pos: jax.Array,         # [B] write positions
) -> jax.Array:
    page = pages.shape[1]
    rows = jnp.arange(row.shape[0])
    return pages.at[page_table[rows, pos // page], pos % page].set(
        row.astype(pages.dtype))
