"""Core layer library: RMSNorm, RoPE (with YaRN scaling), GQA attention
(full / sliding / cross, with KV cache), multi-head latent attention
over a latent cache, SwiGLU MLP, embeddings.

Pure functions over param pytrees.  Activations are annotated with
*logical* axis names via ``repro.distributed.shard`` — no-ops on a single
device, resolved to physical mesh axes by the launcher's rule set.

Dtype policy: params are created in ``param_dtype``; compute runs in
``compute_dtype`` (bf16 on TPU): each weight is cast to the activation's
dtype where the two meet, so the residual stream and the KV caches stay
in the compute dtype whatever the params are held in.  Softmax /
normalization statistics and the final logits are fp32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.config.base import ArchConfig, AttentionKind, LayerSpec, YarnScaling
from repro.distributed.sharding import shard
from repro.kernels import platform
from repro.kernels.decode_attention import (
    paged_decode_attention,
    paged_kv_append,
    paged_latent_append,
    paged_latent_decode,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class PagedSpec:
    """Layout of the shared KV page pool (per attention layer).

    Each attention layer's ``k_pages`` and ``v_pages`` are ``[num_pages,
    page_size, Hkv * head_dim]``: one row per token with every kv head's
    lanes side by side, row-major, as the paged kernels read them.  The
    scanned layers' pools are stacked ``[n_periods, ...]``, and the
    decode scan hands each layer the whole stack with its index under
    the cache's ``"layer"`` key (see ``_pool_at_layer``).

    ``num_pages`` counts the whole pool including page 0, which is
    reserved as a scratch page: inactive batcher slots keep an all-zero
    page table, so their masked-out garbage writes land in page 0 and can
    never corrupt a live slot's cache.  Real slots are only ever handed
    pages >= 1 by the serving ``PagePool``.
    """

    num_pages: int
    page_size: int = 16

    def __post_init__(self):
        if self.page_size <= 0:
            raise ValueError(f"page_size must be positive, got {self.page_size}")
        if self.num_pages < 2:
            raise ValueError(
                "num_pages must be >= 2 (page 0 is the reserved scratch page)"
            )

    def pages_per_slot(self, max_len: int) -> int:
        return -(-max_len // self.page_size)

# Attention implementation selector: "dense" materializes the [T, S]
# score matrix (baseline); "blockwise" runs the flash-attention online-
# softmax recurrence over KV blocks in pure jnp — same math as the
# Pallas kernel, O(block) score residency instead of O(S). Selected per
# run (the §Perf prefill cells are score-memory-bound at 32k).
import contextvars
from contextlib import contextmanager

_attn_impl = contextvars.ContextVar("attention_impl", default="dense")
_attn_block = contextvars.ContextVar("attention_block", default=2048)


@contextmanager
def attention_implementation(name: str, block: int = 2048):
    if name not in ("dense", "blockwise"):
        raise ValueError(f"unknown attention impl {name!r}")
    t1 = _attn_impl.set(name)
    t2 = _attn_block.set(block)
    try:
        yield
    finally:
        _attn_impl.reset(t1)
        _attn_block.reset(t2)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng: jax.Array, shape: Tuple[int, ...], dtype, fan_in: int) -> jax.Array:
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * scale).astype(dtype)


def embed_init(rng: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rotary
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor ``0.1 m ln(s) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(theta: float, dim: int, y: YarnScaling) -> np.ndarray:
    """YaRN's rotary frequencies (DeepSeek-V2's ``yarn`` rope scaling):
    the base frequencies where a dimension turns more than ``beta_fast``
    times over the original length, those divided by ``factor`` where it
    turns fewer than ``beta_slow`` times, a linear ramp between."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns(rotations: float) -> float:
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns(y.beta_fast)), 0)
    high = min(math.ceil(turns(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / y.factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def rope(
    x: jax.Array, positions: jax.Array, theta: float, head_dim: int,
    scaling: Optional[YarnScaling] = None,
) -> jax.Array:
    """Rotary embedding, rotating the two halves of each head.
    x: [B, T, H, D], positions: [B, T]."""
    half = head_dim // 2
    if scaling is None:
        freqs = jnp.exp(
            -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
        )
    else:
        freqs = jnp.asarray(yarn_inv_freq(theta, head_dim, scaling))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,T,1,half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 4)
    return {
        "wq": dense_init(ks[0], (d, h, hd), dtype, d),
        "wk": dense_init(ks[1], (d, hkv, hd), dtype, d),
        "wv": dense_init(ks[2], (d, hkv, hd), dtype, d),
        "wo": dense_init(ks[3], (h, hd, d), dtype, h * hd),
    }


def _attn_weights_mask(
    q_pos: jax.Array,  # [B, Tq]
    kv_pos: jax.Array,  # [B, Tkv]
    window: int,
    causal: bool,
) -> jax.Array:
    """[B, 1, Tq, Tkv] boolean mask (True = attend)."""
    q = q_pos[:, :, None]
    k = kv_pos[:, None, :]
    ok = jnp.ones(q.shape[:1] + (q.shape[1], k.shape[2]), dtype=bool)
    if causal:
        ok = ok & (k <= q)
    if window > 0:
        ok = ok & (k > q - window)
    return ok[:, None, :, :]


def attention(
    params: Params,
    x: jax.Array,  # [B, Tq, D]
    positions: jax.Array,  # [B, Tq]
    cfg: ArchConfig,
    spec: LayerSpec,
    cache: Optional[Params] = None,  # {"k","v": [B, Tkv, Hkv, hd], "pos": [B]}
    kv_x: Optional[jax.Array] = None,  # cross-attention source [B, Tkv, D]
) -> Tuple[jax.Array, Optional[Params]]:
    """GQA attention with optional sliding window, KV cache, cross-attn.

    Returns (output [B,Tq,D], updated cache or None).
    """
    hd = cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    groups = h // hkv
    b, tq, _ = x.shape
    cross = spec.attention == AttentionKind.CROSS and kv_x is not None

    dt = x.dtype
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    q = shard(q, "batch", "seq_inner", "heads", "head_dim")
    src = kv_x if cross else x
    k = jnp.einsum("btd,dhk->bthk", src, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", src, params["wv"].astype(dt))
    k = shard(k, "batch", "seq_inner", "kv_heads", "kv_head_dim")
    v = shard(v, "batch", "seq_inner", "kv_heads", "kv_head_dim")

    if not cross:
        q = rope(q, positions, cfg.rope_theta, hd)
        k = rope(k, positions, cfg.rope_theta, hd)

    new_cache: Optional[Params] = None
    if cache is not None and not cross and "page_table" in cache:
        # Paged KV cache (serving hot path): K/V live in a shared page
        # pool indexed through per-slot page tables; the dense [B, S]
        # cache is never materialized on the decode fast path.
        out, new_cache = _paged_attention(q, k, v, positions, cfg, spec, cache)
        out = out.reshape(b, tq, h, hd)
        y = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(dt))
        return shard(y, "batch", "seq_inner", "embed"), new_cache
    if cache is not None and not cross and "slot_pos" in cache:
        # Ring-buffer cache (sliding-window layers): W slots, token at
        # absolute position p lives in slot p % W; slot_pos records each
        # slot's absolute position (-1 = never written). The window mask
        # runs on absolute positions, so eviction is implicit.
        #
        # Attention reads concat(ring-before-write, current chunk): the
        # chunk's own K/V must be visible to in-chunk queries (a long
        # prefill overwrites the ring several times, but queries need the
        # in-chunk context regardless), and the pre-write ring holds the
        # previous chunk's tail for the cross-chunk window.
        prev_k, prev_v = cache["k"], cache["v"]
        slot_pos, cache_pos = cache["slot_pos"], cache["pos"]
        w = prev_k.shape[1]

        attn_k = jnp.concatenate([prev_k, k], axis=1)
        attn_v = jnp.concatenate([prev_v, v], axis=1)
        chunk_pos = cache_pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
        kv_pos = jnp.concatenate([slot_pos, chunk_pos], axis=1)
        valid = kv_pos >= 0

        # Write the chunk's newest W tokens into the ring (slice first so
        # scatter indices stay unique — duplicate-index order is
        # unspecified).
        k_w, v_w = (k[:, -w:], v[:, -w:]) if tq >= w else (k, v)
        n_w = k_w.shape[1]
        off = tq - n_w

        def ring_write(ck, cv, sp, kk, vv, st):
            abs_pos = st + off + jnp.arange(n_w)
            slots = abs_pos % w
            return (
                ck.at[slots].set(kk),
                cv.at[slots].set(vv),
                sp.at[slots].set(abs_pos),
            )

        new_k, new_v, new_slot_pos = jax.vmap(ring_write)(
            prev_k, prev_v, slot_pos, k_w, v_w, cache_pos
        )
        new_cache = {"k": new_k, "v": new_v, "slot_pos": new_slot_pos,
                     "pos": cache_pos + tq}
        k, v = attn_k, attn_v
    elif cache is not None and not cross:
        # Decode / incremental: write new K,V at each row's own position
        # (continuous batching makes positions ragged across the batch).
        cache_k, cache_v, cache_pos = cache["k"], cache["v"], cache["pos"]
        row_update = jax.vmap(
            lambda ck, kk, st: jax.lax.dynamic_update_slice(ck, kk, (st, 0, 0))
        )
        cache_k = row_update(cache_k, k, cache_pos)
        cache_v = row_update(cache_v, v, cache_pos)
        new_cache = {"k": cache_k, "v": cache_v, "pos": cache_pos + tq}
        k, v = cache_k, cache_v
        kv_pos = jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=positions.dtype)[None, :], (b, k.shape[1])
        )
        valid = kv_pos < (cache_pos[:, None] + tq)
    elif cross:
        kv_pos = jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=positions.dtype)[None, :], (b, k.shape[1])
        )
        valid = jnp.ones_like(kv_pos, dtype=bool)
    else:
        kv_pos = positions
        valid = jnp.ones_like(kv_pos, dtype=bool)

    causal = not cross
    window = spec.window if spec.attention == AttentionKind.SLIDING else 0

    # [B, Tq, G*Hkv, hd] -> grouped [B, Tq, Hkv, G, hd].
    qg = q.reshape(b, tq, hkv, groups, hd)
    block = _attn_block.get()
    if _attn_impl.get() == "blockwise" and k.shape[1] > block:
        out = _blockwise_attention(
            qg, k, v, positions, kv_pos, valid, cfg, window, causal, block
        )
    else:
        out = _dense_attention(
            qg, k, v, positions, kv_pos, valid, cfg, window, causal
        )
    out = out.reshape(b, tq, h, hd)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(dt))
    return shard(y, "batch", "seq_inner", "embed"), new_cache


def _dense_attention(qg, k, v, positions, kv_pos, valid, cfg, window, causal):
    """Materializes the [Tq, S] scores — fine for short S.

    bf16 operands + f32 accumulation (MXU semantics). Upcasting the
    operands instead (astype f32) materializes an f32 copy of the whole
    KV cache — on the sharded decode path GSPMD then all-gathered ~1 TB
    of f32 cache per layer (§Perf cell B iteration 3)."""
    b, tq, hkv, groups, hd = qg.shape
    logits = jnp.einsum(
        "bthgk,bshk->bhgts", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    mask = _attn_weights_mask(positions, kv_pos, window, causal)  # [B,1,Tq,Tkv]
    mask = mask & valid[:, None, None, :]
    mask = mask[:, :, None, :, :]  # [B,1,1,Tq,Tkv] broadcasting over (hkv, g)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bhgts,bshk->bthgk", probs, v, preferred_element_type=jnp.float32
    ).astype(v.dtype)
    return out


def _blockwise_attention(qg, k, v, positions, kv_pos, valid, cfg, window,
                         causal, block):
    """Online-softmax over KV blocks (flash recurrence, pure jnp).

    Score residency drops from O(Tq*S) to O(Tq*block) — at 32k prefill
    the dense scores were the dominant HBM term (§Perf cell A iteration
    4). Same math as kernels/flash_attention, expressed as a lax.scan so
    the dry-run measures its real memory profile."""
    b, tq, hkv, groups, hd = qg.shape
    s = k.shape[1]
    pad = (-s) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))  # False padding
    nb = k.shape[1] // block
    kb = k.reshape(b, nb, block, hkv, hd).swapaxes(0, 1)
    vb = v.reshape(b, nb, block, hkv, hd).swapaxes(0, 1)
    pb = kv_pos.reshape(b, nb, block).swapaxes(0, 1)
    mb = valid.reshape(b, nb, block).swapaxes(0, 1)

    m0 = jnp.full((b, hkv, groups, tq), -1e30, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, groups, tq), dtype=jnp.float32)
    acc0 = jnp.zeros((b, tq, hkv, groups, hd), dtype=jnp.float32)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kc, vc, pc, mc = inp  # [b, block, hkv, hd], ..., [b, block]
        s_blk = jnp.einsum(
            "bthgk,bshk->bhgts", qg, kc, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        if cfg.logit_softcap > 0:
            s_blk = cfg.logit_softcap * jnp.tanh(s_blk / cfg.logit_softcap)
        mask = _attn_weights_mask(positions, pc, window, causal)
        mask = (mask & mc[:, None, None, :])[:, :, None, :, :]
        s_blk = jnp.where(mask, s_blk, -1e30)
        m_cur = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s_blk - m_cur[..., None])
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhgts,bshk->bthgk", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_cur, l_new, acc_new), None

    (m_f, l_f, acc_f), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, pb, mb))
    denom = jnp.maximum(l_f, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (acc_f / denom).astype(v.dtype)


def _pool_at_layer(cache: Params, key: str) -> Tuple[jax.Array, Any, bool]:
    """``(pool, layer, stacked)`` for the paged cache entry's pool
    ``cache[key]``: a scanned layer's entry holds the stacked ``[L, P,
    page, W]`` pool and its index under ``"layer"``; any other layer's
    pool ``[P, page, W]`` is the free view ``pool[None]`` at layer 0,
    so every pool takes one path."""
    pool = cache[key]
    if "layer" in cache:
        return pool, cache["layer"], True
    return pool[None], 0, False


def _scatter_to_pages(pages: jax.Array, layer, new: jax.Array,
                      flat_idx: jax.Array) -> jax.Array:
    """Write token rows into layer ``layer`` of a stacked page pool at
    flat (page*size+offset) slots, in place in the pool.

    pages [L, P, page, Hkv*hd], new [N, Hkv*hd], flat_idx [N]."""
    page = pages.shape[2]
    return pages.at[layer, flat_idx // page, flat_idx % page].set(new)


def _gather_layer(pages: jax.Array, layer, page_table: jax.Array) -> jax.Array:
    """The dense rows layer ``layer``'s page table describes: pages [L,
    P, page, W] + table [B, n] -> [B, n*page, W], gathered straight from
    the stacked pool (the layer's pool is never sliced out whole)."""
    b, n = page_table.shape
    return pages[layer, page_table].reshape(b, n * pages.shape[2],
                                            pages.shape[3])


def _flat_slots(page_table: jax.Array, cache_pos: jax.Array, tq: int,
                page: int) -> jax.Array:
    """Flat pool rows (page * size + offset) of each row's next ``tq``
    positions through its page table, ``[B * tq]``; positions past the
    table land in the scratch page 0."""
    b, n_slot = page_table.shape
    rows = jnp.arange(b)
    pos_bt = cache_pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    in_range = pos_bt < n_slot * page
    page_ids = jnp.where(
        in_range,
        page_table[rows[:, None], jnp.clip(pos_bt // page, 0, n_slot - 1)],
        0,
    )
    return (page_ids * page + pos_bt % page).reshape(-1)


def _paged_attention(
    q: jax.Array,  # [B, Tq, H, hd] (post-rope)
    k: jax.Array,  # [B, Tq, Hkv, hd] (post-rope)
    v: jax.Array,  # [B, Tq, Hkv, hd]
    positions: jax.Array,  # [B, Tq]
    cfg: ArchConfig,
    spec: LayerSpec,
    cache: Params,
) -> Tuple[jax.Array, Params]:
    """Attention against a paged KV cache.

    Decode (Tq == 1) on a TPU runs the fused Pallas path: in-place
    kv-append into the page the slot's table points at, then
    flash-decoding whose KV gather follows the page table inside the
    kernel's DMA schedule.  Prefill (Tq > 1), decode on a backend without
    compiled kernels, and models with a logit softcap (the kernel does
    not implement it) scatter into the pool and attend over the gathered
    dense view — the reference semantics.
    """
    b, tq, h, hd = q.shape
    hkv = k.shape[2]
    k_pages, layer, stacked = _pool_at_layer(cache, "k_pages")
    v_pages, _, _ = _pool_at_layer(cache, "v_pages")
    page_table, cache_pos = cache["page_table"], cache["pos"]
    page = k_pages.shape[2]
    n_slot = page_table.shape[1]
    s_slot = n_slot * page
    window = spec.window if spec.attention == AttentionKind.SLIDING else 0
    kv_len = cache_pos + tq

    if tq == 1 and platform.compiled_kernels() and cfg.logit_softcap == 0:
        k_pages, v_pages = paged_kv_append(
            k[:, 0], v[:, 0], k_pages, v_pages, page_table, cache_pos, layer
        )
        out = paged_decode_attention(
            q[:, 0], k_pages, v_pages, page_table, kv_len, layer,
            window=window,
        )
        out = out[:, None].astype(v.dtype)  # [B, 1, H, hd]
    else:
        # Scatter the chunk through the page tables (prefill, or the
        # softcap / jnp decode path), then attend over the gathered
        # dense view of each slot's pages.
        flat_idx = _flat_slots(page_table, cache_pos, tq, page)
        k_pages = _scatter_to_pages(
            k_pages, layer, k.reshape(b * tq, hkv * hd), flat_idx
        )
        v_pages = _scatter_to_pages(
            v_pages, layer, v.reshape(b * tq, hkv * hd), flat_idx
        )
        k_dense = _gather_layer(k_pages, layer, page_table).reshape(
            b, s_slot, hkv, hd)
        v_dense = _gather_layer(v_pages, layer, page_table).reshape(
            b, s_slot, hkv, hd)
        kv_pos = jnp.broadcast_to(
            jnp.arange(s_slot, dtype=positions.dtype)[None, :], (b, s_slot)
        )
        valid = kv_pos < kv_len[:, None]
        qg = q.reshape(b, tq, hkv, h // hkv, hd)
        out = _dense_attention(
            qg, k_dense, v_dense, positions, kv_pos, valid, cfg, window, True
        )

    new_cache = {
        "k_pages": k_pages if stacked else k_pages[0],
        "v_pages": v_pages if stacked else v_pages[0],
        "page_table": page_table,
        "pos": kv_len,
    }
    return out, new_cache


def init_attention_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype, ring_window: int = 0,
    paged: Optional[PagedSpec] = None,
) -> Params:
    """ring_window > 0: W-slot ring buffer for a sliding-window layer
    (W >= window); otherwise a full-length linear cache.  ``paged``
    overrides both with a shared page pool + per-slot page tables (the
    table rows start at 0, i.e. pointing at the reserved scratch page —
    the serving layer assigns real pages at admission)."""
    hd = cfg.resolved_head_dim
    if paged is not None:
        n_slot = paged.pages_per_slot(max_len)
        # Lane-dense rows: a token's every kv head side by side, the
        # layout the paged kernels read and write in place.
        pool = (paged.num_pages, paged.page_size, cfg.num_kv_heads * hd)
        return {
            "k_pages": jnp.zeros(pool, dtype=dtype),
            "v_pages": jnp.zeros(pool, dtype=dtype),
            "page_table": jnp.zeros((batch, n_slot), dtype=jnp.int32),
            "pos": jnp.zeros((batch,), dtype=jnp.int32),
        }
    size = min(ring_window, max_len) if ring_window > 0 else max_len
    cache = {
        "k": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dtype=dtype),
        "v": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dtype=dtype),
        "pos": jnp.zeros((batch,), dtype=jnp.int32),
    }
    if ring_window > 0 and size < max_len:
        cache["slot_pos"] = jnp.full((batch, size), -1, dtype=jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
#
# Each token's keys and values come from one latent c = RMSNorm(x W_DKV)
# of kv_lora_rank lanes, up-projected per head (k_nope = c W_UK, v = c
# W_UV), beside one rope key k_r = rope(x W_KR) that every head shares.
# The cache therefore holds one row per token, [c ; k_r], zero-padded to
# whole 128-lane tiles (``latent_width``): 512 + 64 -> 640 lanes at the
# published widths.  Prefill up-projects the rows block by block; decode
# absorbs W_UK into the query (q_nope W_UK^T, kv_lora_rank lanes), scores
# it with q_rope against the whole row, and keeps the output in the latent
# space until W_UV.


LANES = 128


def latent_width(cfg: ArchConfig) -> int:
    """Lanes of one cached latent row: latent then rope key, padded."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.qk_rope_head_dim) // LANES) * LANES


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times YaRN's ``mscale_all_dim``
    factor squared where the rope is scaled (DeepSeek-V2)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_latent_attention(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    r, dn, dr, dv = (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
                     m.v_head_dim)
    ks = jax.random.split(rng, 5)
    return {
        "wq": dense_init(ks[0], (d, h, dn + dr), dtype, d),
        "wkv_a": dense_init(ks[1], (d, r + dr), dtype, d),
        "kv_norm": jnp.zeros((r,), dtype=dtype),
        "wk_b": dense_init(ks[2], (r, h, dn), dtype, r),
        "wv_b": dense_init(ks[3], (r, h, dv), dtype, r),
        "wo": dense_init(ks[4], (h, dv, d), dtype, h * dv),
    }


def init_latent_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                      paged: Optional[PagedSpec] = None) -> Params:
    """A latent row per token: a shared page pool ``[P, page, width]``
    behind per-slot page tables, or ``[B, max_len, width]`` rows."""
    w = latent_width(cfg)
    if paged is not None:
        return {
            "latent_pages": jnp.zeros(
                (paged.num_pages, paged.page_size, w), dtype=dtype),
            "page_table": jnp.zeros(
                (batch, paged.pages_per_slot(max_len)), dtype=jnp.int32),
            "pos": jnp.zeros((batch,), dtype=jnp.int32),
        }
    return {"latent": jnp.zeros((batch, max_len, w), dtype=dtype),
            "pos": jnp.zeros((batch,), dtype=jnp.int32)}


def latent_attention(
    params: Params,
    x: jax.Array,  # [B, Tq, D]
    positions: jax.Array,  # [B, Tq]
    cfg: ArchConfig,
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """MLA over a latent cache (paged or rows), or over the chunk itself
    (training).  Returns (output [B, Tq, D], updated cache or None)."""
    m, dt = cfg.mla, x.dtype
    r, dn, dr = m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim
    b, tq, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    q_nope = q[..., :dn]
    q_rope = rope(q[..., dn:], positions, cfg.rope_theta, dr, cfg.rope_scaling)
    kv = jnp.einsum("btd,dk->btk", x, params["wkv_a"].astype(dt))
    c = rms_norm(kv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_rope = rope(kv[..., None, r:], positions, cfg.rope_theta, dr,
                  cfg.rope_scaling)[:, :, 0]
    w = latent_width(cfg)
    row = jnp.concatenate(
        [c, k_rope.astype(dt), jnp.zeros((b, tq, w - r - dr), dt)], axis=-1)

    new_cache: Optional[Params] = None
    if cache is None:
        out = _latent_attend(params, q_nope, q_rope, row, positions,
                             positions[:, -1] + 1, cfg)
    elif "page_table" in cache:
        pages, layer, stacked = _pool_at_layer(cache, "latent_pages")
        table, pos = cache["page_table"], cache["pos"]
        kv_len = pos + tq
        if tq == 1 and platform.compiled_kernels():
            pages = paged_latent_append(row[:, 0], pages, table, pos, layer)
            q_lat = _absorb(params, q_nope)[:, 0]  # [B, H, r]
            qf = jnp.concatenate(
                [q_lat, q_rope[:, 0].astype(dt),
                 jnp.zeros((b, q.shape[2], w - r - dr), dt)], axis=-1)
            o_lat = paged_latent_decode(
                qf.astype(pages.dtype), pages, table, kv_len, layer,
                value_dim=r, sm_scale=mla_softmax_scale(cfg))
            out = _unabsorb(params, o_lat[:, None].astype(dt))
        else:
            flat = _flat_slots(table, pos, tq, pages.shape[2])
            pages = _scatter_to_pages(
                pages, layer, row.reshape(b * tq, w).astype(pages.dtype), flat)
            out = _latent_attend(params, q_nope, q_rope,
                                 _gather_layer(pages, layer, table), positions,
                                 kv_len, cfg)
        new_cache = {"latent_pages": pages if stacked else pages[0],
                     "page_table": table, "pos": kv_len}
    else:
        pos = cache["pos"]
        rows = jax.vmap(
            lambda cr, nr, st: jax.lax.dynamic_update_slice(cr, nr, (st, 0))
        )(cache["latent"], row.astype(cache["latent"].dtype), pos)
        out = _latent_attend(params, q_nope, q_rope, rows, positions,
                             pos + tq, cfg)
        new_cache = {"latent": rows, "pos": pos + tq}

    y = jnp.einsum("bthv,hvd->btd", out, params["wo"].astype(dt))
    return shard(y, "batch", "seq_inner", "embed"), new_cache


def _absorb(params: Params, q_nope: jax.Array) -> jax.Array:
    """q_nope W_UK^T: [B, T, H, qk_nope] -> [B, T, H, kv_lora_rank]."""
    dt = q_nope.dtype
    return jnp.einsum("bthn,rhn->bthr", q_nope, params["wk_b"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


def _unabsorb(params: Params, o_lat: jax.Array) -> jax.Array:
    """The latent-space output times W_UV: [B, T, H, r] -> [B, T, H, v]."""
    dt = o_lat.dtype
    return jnp.einsum("bthr,rhv->bthv", o_lat, params["wv_b"].astype(dt))


def _latent_attend(params, q_nope, q_rope, rows, q_pos, kv_len, cfg):
    """Attention of queries at ``q_pos`` over cached rows ``[B, S, width]``
    at positions 0..S-1, of which each row's first ``kv_len`` are live:
    the absorbed form for one query (decode), the rows up-projected
    block by block for a longer chunk."""
    if q_nope.shape[1] == 1:
        return absorbed_attend(params, q_nope, q_rope, rows, kv_len, cfg)
    return upprojected_attend(params, q_nope, q_rope, rows, q_pos, kv_len, cfg)


def absorbed_attend(params, q_nope, q_rope, rows, kv_len, cfg):
    """One query per row: q_nope W_UK^T and q_rope scored against each
    row's latent and rope key, the output kept in the latent space until
    W_UV.  What the paged latent kernel computes, in jnp."""
    m, dt = cfg.mla, q_nope.dtype
    r, dr = m.kv_lora_rank, m.qk_rope_head_dim
    rows = rows.astype(dt)
    c, k_r = rows[..., :r], rows[..., r:r + dr]
    s = (jnp.einsum("bthr,bsr->bhts", _absorb(params, q_nope), c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthk,bsk->bhts", q_rope.astype(dt), k_r,
                      preferred_element_type=jnp.float32)
         ) * mla_softmax_scale(cfg)
    live = (jnp.arange(rows.shape[1])[None, :] < kv_len[:, None])
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, -1e30), axis=-1)
    o_lat = jnp.einsum("bhts,bsr->bhtr", p.astype(dt), c,
                       preferred_element_type=jnp.float32)
    return _unabsorb(params, o_lat.swapaxes(1, 2).astype(dt))


def upprojected_attend(params, q_nope, q_rope, rows, q_pos, kv_len, cfg,
                       block: int = 512):
    """Keys and values up-projected from the rows ``block`` at a time
    under the online softmax, so a 7k-token prompt holds [H, Tq, block]
    scores and not [H, Tq, S]; blocks past the longest row's ``kv_len``
    are skipped."""
    m, dt = cfg.mla, q_nope.dtype
    r, dr = m.kv_lora_rank, m.qk_rope_head_dim
    scale = mla_softmax_scale(cfg)
    b, tq, h, _ = q_nope.shape
    rows = rows.astype(dt)
    block = min(block, rows.shape[1])
    pad = (-rows.shape[1]) % block
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
    n_blocks = rows.shape[1] // block
    n_live = (jnp.max(kv_len) + block - 1) // block
    wk, wv = params["wk_b"].astype(dt), params["wv_b"].astype(dt)
    q_rope = q_rope.astype(dt)

    def update(i, carry):
        m_prev, l_prev, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(rows, i * block, block, axis=1)
        k_nope = jnp.einsum("bsr,rhn->bshn", blk[..., :r], wk)
        v = jnp.einsum("bsr,rhv->bshv", blk[..., :r], wv)
        s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthk,bsk->bhts", q_rope, blk[..., r:r + dr],
                          preferred_element_type=jnp.float32)) * scale
        k_pos = i * block + jnp.arange(block)
        mask = ((k_pos[None, None, :] <= q_pos[:, :, None])
                & (k_pos[None, None, :] < kv_len[:, None, None]))[:, None]
        s = jnp.where(mask, s, -1e30)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur[..., None]), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhts,bshv->bthv", p.astype(dt), v,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_cur, l_new, acc

    def step(carry, i):
        return jax.lax.cond(i < n_live, lambda c: update(i, c),
                            lambda c: c, carry), None

    init = (jnp.full((b, h, tq), -1e30, jnp.float32),
            jnp.zeros((b, h, tq), jnp.float32),
            jnp.zeros((b, tq, h, m.v_head_dim), jnp.float32))
    (_, l_f, acc), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    denom = jnp.maximum(l_f, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(dt)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(rng: jax.Array, cfg: ArchConfig, dtype,
             ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(ks[0], (d, ff), dtype, d),
        "w_up": dense_init(ks[1], (d, ff), dtype, d),
        "w_down": dense_init(ks[2], (ff, d), dtype, ff),
    }


def mlp(params: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    gate = jnp.einsum("btd,df->btf", x, params["w_gate"].astype(dt))
    up = jnp.einsum("btd,df->btf", x, params["w_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    h = shard(h, "batch", "seq_inner", "ffn")
    return jnp.einsum("btf,fd->btd", h, params["w_down"].astype(dt))


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def init_embedding(rng: jax.Array, cfg: ArchConfig, dtype) -> Params:
    p = {"tok": embed_init(rng, (cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = embed_init(
            jax.random.fold_in(rng, 1), (cfg.d_model, cfg.vocab_size), dtype
        )
    return p


def embed(params: Params, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    x = jnp.take(params["tok"], tokens, axis=0)
    if cfg.tie_embeddings:
        x = x * math.sqrt(cfg.d_model)  # gemma-style scaling for tied embeds
    return shard(x, "batch", "seq", "embed")


def unembed(params: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    # compute-dtype operands, f32 accumulation: upcasting the embedding
    # table would materialize an f32 copy of the largest matrix in the
    # model (gemma3: 262k x 2560).
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "btd,vd->btv", x, params["tok"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    else:
        logits = jnp.einsum(
            "btd,dv->btv", x, params["unembed"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return shard(logits, "batch", "seq", "vocab")
