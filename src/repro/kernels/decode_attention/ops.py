"""Public wrappers for the decode attention kernels (dense and paged).

Validation happens here, eagerly, before anything is traced:

  * ``kv_len`` / ``page_table`` must be integer-typed — a float length
    silently truncates toward whatever ``astype(int32)`` does, so it is
    rejected with a ``TypeError`` instead of cast.
  * Concrete (non-tracer) ``kv_len`` values are range-checked against
    the cache: ``kv_len > S`` would *silently attend garbage rows* (the
    kernel masks ``k_pos < kv_len`` — rows in ``[S, kv_len)`` simply do
    not exist, so nothing masks them out of a bigger cache).  Traced
    values cannot be inspected; they are clamped defensively instead.
  * A paged pool is the stacked ``[L, P, page, W]`` of every layer and
    the call names its layer; a concrete layer is range-checked, a
    traced one clamped.  A single layer's pool is the free view
    ``pool[None]`` at layer 0.
  * ``block_k`` is aligned to the TPU lane width (128) rather than a
    bare ``min(block_k, S)``: the largest multiple of 128 that divides
    ``S`` and fits the request, falling back to the largest divisor of
    ``S`` when ``S`` itself is not 128-aligned (interpret-mode tests use
    such shapes; hardware callers should keep ``S % 128 == 0``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention.kernel import (
    LANE,
    decode_attention_fwd,
    paged_decode_attention_fwd,
    paged_kv_append_fwd,
    paged_latent_append_fwd,
    paged_latent_decode_fwd,
)
from repro.kernels.platform import resolve_interpret


def _require_int(name: str, arr: jax.Array) -> jax.Array:
    if not jnp.issubdtype(arr.dtype, jnp.integer):
        raise TypeError(
            f"{name} must be integer-typed (got {arr.dtype}); a float "
            "length would be truncated silently"
        )
    return arr.astype(jnp.int32)


def _check_concrete_range(name: str, arr: jax.Array, upper: int) -> None:
    """Range-check eager values; traced values pass (clamped later)."""
    if isinstance(arr, jax.core.Tracer):
        return
    vals = np.asarray(arr)
    if vals.size == 0:
        return
    if vals.min() < 0:
        raise ValueError(f"{name} has negative entries (min={vals.min()})")
    if vals.max() > upper:
        raise ValueError(
            f"{name} exceeds the cache: max={vals.max()} > {upper}; the "
            "kernel would silently attend rows that do not exist"
        )


def align_block_k(block_k: int, s: int) -> int:
    """Largest hardware-aligned KV block that tiles ``S`` exactly.

    Prefers multiples of the 128-lane width; when ``S`` has no 128-
    aligned divisor ≤ the request, falls back to the largest divisor of
    ``S`` that fits (never a bare ``min`` that might not divide S)."""
    if block_k <= 0:
        raise ValueError(f"block_k must be positive, got {block_k}")
    cap = min(block_k, s)
    aligned = [
        bk for bk in range(LANE, cap + 1, LANE) if s % bk == 0
    ]
    if aligned:
        return aligned[-1]
    return max(bk for bk in range(1, cap + 1) if s % bk == 0)


@functools.partial(
    jax.jit,
    static_argnames=("window", "sm_scale", "block_k", "interpret"),
)
def _decode_attention_jit(q, k_cache, v_cache, kv_len, window, sm_scale,
                          block_k, interpret):
    return decode_attention_fwd(
        q, k_cache, v_cache, kv_len,
        window=window, sm_scale=sm_scale, block_k=block_k,
        interpret=interpret,
    )


def decode_attention(
    q: jax.Array,        # [B, H, D]
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,  # [B, S, Hkv, D]
    kv_len: jax.Array,   # [B]
    window: int = 0,
    sm_scale: Optional[float] = None,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if q.ndim != 3:
        raise ValueError("q must be [B, H, D] (one token per sequence)")
    if q.shape[1] % k_cache.shape[2] != 0:
        raise ValueError("num_heads must be a multiple of num_kv_heads")
    s = k_cache.shape[1]
    kv_len = _require_int("kv_len", kv_len)
    _check_concrete_range("kv_len", kv_len, s)
    kv_len = jnp.clip(kv_len, 0, s)  # traced values: defensive clamp
    bk = align_block_k(block_k, s)
    return _decode_attention_jit(
        q, k_cache, v_cache, kv_len,
        window=window, sm_scale=sm_scale, block_k=bk,
        interpret=resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# paged wrappers
# ---------------------------------------------------------------------------


def _check_paged(name, b, pages, page_table, lens, layer, upper_extra):
    """Validate and contain the page table, the lengths or positions
    ``lens`` and the layer index of a call on the stacked pool ``pages``
    ``[L, P, page, W]``: concrete values are range-checked, traced ones
    (the jitted serving path) clamped."""
    if pages.ndim != 4:
        raise ValueError(
            f"pages must be the stacked pool [L, P, page_size, W], got "
            f"{pages.shape}; one layer's pool is pool[None] at layer 0"
        )
    if page_table.ndim != 2 or page_table.shape[0] != b:
        raise ValueError(
            f"page_table must be [B, n_pages], got {page_table.shape} "
            f"for batch {b}"
        )
    n_layers, n_pool = pages.shape[0], pages.shape[1]
    n_pages, page_size = page_table.shape[1], pages.shape[2]
    lens = _require_int(name, lens)
    page_table = _require_int("page_table", page_table)
    layer = _require_int("layer", jnp.asarray(layer))
    if layer.ndim != 0:
        raise ValueError(f"layer must be a scalar, got shape {layer.shape}")
    _check_concrete_range(name, lens, n_pages * page_size - upper_extra)
    _check_concrete_range("page_table", page_table, n_pool - 1)
    _check_concrete_range("layer", layer, n_layers - 1)
    lens = jnp.clip(lens, 0, n_pages * page_size - upper_extra)
    page_table = jnp.clip(page_table, 0, n_pool - 1)
    layer = jnp.clip(layer, 0, n_layers - 1)
    return lens, page_table, layer


@functools.partial(
    jax.jit, static_argnames=("window", "sm_scale", "interpret")
)
def _paged_decode_jit(q, k_pages, v_pages, page_table, kv_len, layer,
                      window, sm_scale, interpret):
    return paged_decode_attention_fwd(
        q, k_pages, v_pages, page_table, kv_len, layer,
        window=window, sm_scale=sm_scale, interpret=interpret,
    )


def paged_decode_attention(
    q: jax.Array,           # [B, H, D]
    k_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    v_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    page_table: jax.Array,  # [B, n_pages] int32
    kv_len: jax.Array,      # [B]
    layer,                  # int scalar: the pool's layer
    window: int = 0,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One query token per sequence against layer ``layer`` of the
    lane-dense stacked page pool: each pool row holds every kv head's D
    lanes side by side."""
    if q.ndim != 3:
        raise ValueError("q must be [B, H, D] (one token per sequence)")
    if k_pages.ndim != 4 or k_pages.shape[3] % q.shape[2] != 0:
        raise ValueError(
            f"k_pages must be [L, P, page_size, Hkv*D] with D = "
            f"{q.shape[2]}, got {k_pages.shape}"
        )
    if q.shape[1] % (k_pages.shape[3] // q.shape[2]) != 0:
        raise ValueError("num_heads must be a multiple of num_kv_heads")
    kv_len, page_table, layer = _check_paged(
        "kv_len", q.shape[0], k_pages, page_table, kv_len, layer, 0)
    return _paged_decode_jit(
        q, k_pages, v_pages, page_table, kv_len, layer,
        window=window, sm_scale=sm_scale,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_append_jit(k_new, v_new, k_pages, v_pages, page_table, pos, layer,
                   interpret):
    return paged_kv_append_fwd(
        k_new, v_new, k_pages, v_pages, page_table, pos, layer,
        interpret=interpret,
    )


def paged_kv_append(
    k_new: jax.Array,       # [B, Hkv, D]
    v_new: jax.Array,       # [B, Hkv, D]
    k_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    v_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    page_table: jax.Array,  # [B, n_pages] int32
    pos: jax.Array,         # [B] write positions (kv_len before append)
    layer,                  # int scalar: the pool's layer
    interpret: Optional[bool] = None,
) -> "tuple[jax.Array, jax.Array]":
    """Write each sequence's new K/V as one lane-dense row of its page
    in layer ``layer`` of the stacked pools, in place."""
    if k_new.ndim != 3:
        raise ValueError("k_new must be [B, Hkv, D] (one token per sequence)")
    if k_pages.ndim != 4 or k_pages.shape[3] != k_new.shape[1] * k_new.shape[2]:
        raise ValueError(
            f"k_pages must be [L, P, page_size, Hkv*D] for k_new "
            f"{k_new.shape}, got {k_pages.shape}"
        )
    # Traced positions get the same containment kv_len gets in
    # paged_decode_attention: an idle slot's cache pos grows without
    # bound, and unclamped it would walk the kernel's page-table read
    # off the end of the row.  Clamped, the write lands in the slot's
    # own last table entry — the scratch page for an idle (all-zero)
    # table row — never in another slot's pages.
    pos, page_table, layer = _check_paged(
        "pos", k_new.shape[0], k_pages, page_table, pos, layer, 1)
    return _kv_append_jit(
        k_new, v_new, k_pages, v_pages, page_table, pos, layer,
        interpret=resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# paged latent (MLA) wrappers
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("value_dim", "sm_scale", "interpret")
)
def _paged_latent_decode_jit(q, pages, page_table, kv_len, layer, value_dim,
                             sm_scale, interpret):
    return paged_latent_decode_fwd(
        q, pages, page_table, kv_len, layer, value_dim=value_dim,
        sm_scale=sm_scale, interpret=interpret,
    )


def paged_latent_decode(
    q: jax.Array,           # [B, H, W]
    pages: jax.Array,       # [L, P, page_size, W]
    page_table: jax.Array,  # [B, n_pages] int32
    kv_len: jax.Array,      # [B]
    layer,                  # int scalar: the pool's layer
    value_dim: int,
    sm_scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One latent-space query per head and sequence against layer
    ``layer`` of the stacked latent page pool: scores over each row's W
    lanes, the output the weighted sum of its first ``value_dim`` lanes,
    ``[B, H, value_dim]``."""
    if q.ndim != 3 or q.shape[2] != pages.shape[-1]:
        raise ValueError(
            f"q must be [B, H, W] and pages [L, P, page_size, W], got "
            f"{q.shape} and {pages.shape}"
        )
    if not 0 < value_dim <= q.shape[2]:
        raise ValueError(f"value_dim {value_dim} outside the row's "
                         f"{q.shape[2]} lanes")
    kv_len, page_table, layer = _check_paged(
        "kv_len", q.shape[0], pages, page_table, kv_len, layer, 0)
    return _paged_latent_decode_jit(
        q, pages, page_table, kv_len, layer, value_dim=value_dim,
        sm_scale=float(sm_scale), interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _latent_append_jit(row, pages, page_table, pos, layer, interpret):
    return paged_latent_append_fwd(row, pages, page_table, pos, layer,
                                   interpret=interpret)


def paged_latent_append(
    row: jax.Array,         # [B, W]
    pages: jax.Array,       # [L, P, page_size, W]
    page_table: jax.Array,  # [B, n_pages] int32
    pos: jax.Array,         # [B] write positions (kv_len before append)
    layer,                  # int scalar: the pool's layer
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Write each sequence's new latent row into its page in layer
    ``layer`` of the stacked pool, in place; an idle slot's runaway
    position is clamped into its own last table entry, as
    ``paged_kv_append`` clamps it."""
    if row.ndim != 2 or row.shape[1] != pages.shape[-1]:
        raise ValueError(
            f"row must be [B, W] for pages {pages.shape}, got {row.shape}"
        )
    pos, page_table, layer = _check_paged(
        "pos", row.shape[0], pages, page_table, pos, layer, 1)
    return _latent_append_jit(row, pages, page_table, pos, layer,
                              interpret=resolve_interpret(interpret))
