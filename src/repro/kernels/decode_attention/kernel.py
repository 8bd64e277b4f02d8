"""Single-token decode attention kernels (TPU Pallas): dense and paged.

Decode is memory-bound: the whole KV cache streams HBM->VMEM once per
step while compute is a handful of GEMVs.  The kernel therefore optimizes
for exactly one thing: **read each KV block once for the whole GQA
group**.  Grid = (B, Hkv, S/block_k); the q block holds all G = H/Hkv
query heads of the kv head, so arithmetic intensity per KV byte is G x
that of a per-head loop (the flash kernel's schedule).  G x 128-dim GEMVs
also batch into one (G, d) x (d, block_k) MXU matmul.

Running softmax stats (m, l) and the (G, d) accumulator sit in VMEM
scratch across the sequential S-steps, exactly like the flash kernel.
kv_len masking handles ragged batches (continuous batching feeds
sequences of different lengths).

The **paged** variants replace the per-sequence dense cache
``[B, S, Hkv, D]`` with a shared page pool plus a per-sequence page
table ``[B, pages_per_seq]`` — the serving layer allocates pages per
token tick (continuous batching) instead of reserving max_len rows per
slot.  The pool is every layer's, stacked ``[L, P, page_size, Hkv*D]``,
and a call names its layer: the decode scan carries the stacked pool
and updates it in place, where slicing a layer's pool out and writing
it back would copy it twice a layer.  The layer index, the page table
and kv_len ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps gather
the right K/V page of the right layer for every grid step — the gather
happens in the DMA schedule, never as a materialized
``k_pages[layer, page_table]`` copy.  Grid = (B, pages per sequence),
and one K/V block is a whole page, ``(page, Hkv*D)``.

The pool is **lane-dense**: a token's row holds every kv head's D
lanes side by side.  With D = 64 a ``[.., page, Hkv, D]`` pool would
fill half of each 128-lane tile, so XLA laid it out with the page axis
minor-most instead, and every call transposed the whole pool into the
kernel's row-major blocks and back.  ``[P, page, Hkv*D]`` fills whole
tiles row-major (Hkv*D is a multiple of 128 at every served width), so
XLA keeps the layout the kernels read.  Inside the decode kernel each
head's ``q.k`` is a matmul of the page against a block-diagonal copy of
q (see ``_paged_decode_kernel``), and the online softmax keeps its
statistics per lane.  ``paged_kv_append`` writes one new token's K/V
row into its page in place (``input_output_aliases``), so the per-tick
cache update is O(1) rows, not an O(S) re-materialization.  The dense
kernel above stays the bitwise reference path (the ``vectorize=False``
pattern of the vectorized control plane).

``paged_latent_decode`` serves multi-head latent attention (MLA): the
pool holds one row per token, the latent then the shared rope key,
zero-padded to whole lane tiles (``[L, P, page, W]``, W = 640 for 512 + 64),
and every one of the H query heads is a latent-space query of W lanes
(``q_nope W_UK^T`` joined to ``q_rope``).  Scores are ``q @ rows^T`` over
all W lanes, values the rows' first V lanes, the output ``[B, H, V]``.
With one row shared by all H heads the kernel does some 30 FLOP a byte,
so a grid step takes a block of ``pages_per_block`` pages (512 tokens),
each its own page-table-gathered operand, to amortise the step's fixed
cost; a block past the live length is skipped, and its scratch-page
operands are not fetched again.  Its append reuses ``paged_kv_append``'s
kernel on the one pool.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128  # lanes of a vreg


def _decode_kernel(
    kv_len_ref,  # [1] int32 (scalar prefetch-style, small block)
    q_ref,       # [1, 1, G, d]
    k_ref,       # [1, block_k, 1, d]
    v_ref,       # [1, block_k, 1, d]
    o_ref,       # [1, 1, G, d]
    m_ref,       # scratch [G, 1] f32
    l_ref,       # scratch [G, 1] f32
    acc_ref,     # scratch [G, d] f32
    *,
    sm_scale: float,
    window: int,
    block_k: int,
    kv_steps: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, :, :].astype(jnp.float32)  # [G, d]
    k = k_ref[0, :, 0, :].astype(jnp.float32)  # [bk, d]
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = jnp.dot(q, k.T) * sm_scale  # [G, bk] (one MXU matmul per block)

    kv_len = kv_len_ref[0]
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = k_pos < kv_len
    if window > 0:
        mask = mask & (k_pos > kv_len - 1 - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
    m_ref[:, 0] = m_cur

    @pl.when(ik == kv_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention_fwd(
    q: jax.Array,        # [B, H, D]
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,  # [B, S, Hkv, D]
    kv_len: jax.Array,   # [B] int32
    window: int = 0,
    sm_scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    assert s % block_k == 0, (s, block_k)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    kv_steps = s // block_k
    # Head h belongs to kv-head h // g, so [B, H, d] -> [B, Hkv, G, d]
    # groups each kv head's queries contiguously.
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(
        _decode_kernel,
        sm_scale=scale,
        window=window,
        block_k=block_k,
        kv_steps=kv_steps,
    )

    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, kv_steps),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h_, ik: (b_,)),
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, ik: (b_, h_, 0, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda b_, h_, ik: (b_, ik, h_, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda b_, h_, ik: (b_, ik, h_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, ik: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# paged decode: gather K/V pages through a scalar-prefetched page table
# ---------------------------------------------------------------------------


def _head_chunk(head_dim: int, width: int) -> int:
    """Lanes of a pool row the paged decode kernel takes at a time: the
    fewest whole heads that fill whole 128-lane vregs (two heads of 64,
    one of 128), or the whole row where such chunks do not tile it
    (small interpret-mode widths)."""
    chunk = math.lcm(head_dim, LANE)
    return chunk if width % chunk == 0 else width


def _paged_decode_kernel(
    layer_ref,   # scalar prefetch [1] int32 layer of the stacked pool
    pt_ref,      # scalar prefetch [B, n_pages] int32 page table
    kv_len_ref,  # scalar prefetch [B] int32
    q_ref,       # [1, G, Hkv*d]
    k_ref,       # [1, page, Hkv*d]  (page selected by the index map)
    v_ref,       # [1, page, Hkv*d]
    o_ref,       # [1, G, Hkv*d]
    qbd_ref,     # scratch [G * n_chunks, chunk, chunk] block-diagonal q
    m_ref,       # scratch [G, Hkv*d] f32, each head's stat on its d lanes
    l_ref,       # scratch [G, Hkv*d] f32
    acc_ref,     # scratch [G, Hkv*d] f32
    *,
    sm_scale: float,
    window: int,
    page_size: int,
    kv_steps: int,
    groups: int,
    head_dim: int,
    chunk: int,
):
    del layer_ref, pt_ref  # consumed by the index maps
    ib = pl.program_id(0)
    ik = pl.program_id(1)
    n_chunks = acc_ref.shape[1] // chunk

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # qbd[j, l] = q[j] where lanes j and l hold the same head, else 0:
        # ``k_chunk @ qbd`` then gives every lane of a head that head's
        # q.k, so the sum over d runs on the MXU and never leaves the
        # lanes.  Built once per sequence as diag(q) @ same (exact: one
        # nonzero term per output), with no transpose.
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        same = (row // head_dim == col // head_dim).astype(jnp.float32)
        for g in range(groups):
            for c in range(n_chunks):
                q = q_ref[0, g:g + 1, c * chunk:(c + 1) * chunk]
                diag = jnp.where(row == col, q.astype(jnp.float32), 0.0)
                qbd_ref[g * n_chunks + c] = jnp.dot(
                    diag, same, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                ).astype(qbd_ref.dtype)

    kv_len = kv_len_ref[ib]

    # Pages at or past the valid length are fully masked; skip their
    # flash update entirely (the DMA still lands — the wrapper clamps
    # unallocated table entries to a valid page id).
    @pl.when(ik * page_size < kv_len)
    def _update():
        k_pos = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0
        )
        mask = k_pos < kv_len
        if window > 0:
            mask = mask & (k_pos > kv_len - 1 - window)
        # bf16 products are exact in the f32 accumulator; f32 operands
        # need the MXU's multi-pass precision to stay float32.
        precision = (jax.lax.Precision.HIGHEST
                     if qbd_ref.dtype == jnp.float32 else None)
        # Every statistic is held per lane, each head's on its own d
        # lanes, so the online softmax is elementwise over [page, chunk]
        # tiles and p.v a sum over the page's rows.
        for c in range(n_chunks):
            lanes = slice(c * chunk, (c + 1) * chunk)
            k = k_ref[0, :, lanes].astype(qbd_ref.dtype)  # [page, chunk]
            v = v_ref[0, :, lanes].astype(jnp.float32)
            for g in range(groups):
                s = jnp.dot(
                    k, qbd_ref[g * n_chunks + c],
                    preferred_element_type=jnp.float32, precision=precision,
                ) * sm_scale
                s = jnp.where(mask, s, NEG_INF)
                rows = (slice(g, g + 1), lanes)
                m_prev = m_ref[rows]  # [1, chunk]
                m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)
                p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
                l_ref[rows] = l_ref[rows] * alpha + jnp.sum(
                    p, axis=0, keepdims=True)
                acc_ref[rows] = acc_ref[rows] * alpha + jnp.sum(
                    p * v, axis=0, keepdims=True)
                m_ref[rows] = m_cur

    @pl.when(ik == kv_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _layer_operand(layer) -> jax.Array:
    """The layer index as the ``[1]`` int32 scalar-prefetch operand."""
    return jnp.asarray(layer, dtype=jnp.int32).reshape(1)


def paged_decode_attention_fwd(
    q: jax.Array,           # [B, H, D]
    k_pages: jax.Array,     # [L, P, page_size, Hkv*D] stacked page pool
    v_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    page_table: jax.Array,  # [B, n_pages] int32 (page id per logical page)
    kv_len: jax.Array,      # [B] int32
    layer: jax.Array,       # int32 scalar, the pool's layer to read
    window: int = 0,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    page_size, width = k_pages.shape[2], k_pages.shape[3]
    hkv = width // d
    n_pages = page_table.shape[1]
    g = h // hkv
    chunk = _head_chunk(d, width)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    # Head h belongs to kv-head h // g: [B, H, d] -> [B, G, Hkv*d], so
    # group g's row lines its heads up with the pool's lanes.
    qg = q.reshape(b, hkv, g, d).swapaxes(1, 2).reshape(b, g, width)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=scale,
        window=window,
        page_size=page_size,
        kv_steps=n_pages,
        groups=g,
        head_dim=d,
        chunk=chunk,
    )

    # The page-table gather: logical page ik of sequence b_ lives in
    # pool page pt[b_, ik] of the layer's pool — resolved at DMA-schedule
    # time.  One block is a whole page row-major, every kv head on the
    # lanes: the pool's own layout, so nothing is transposed.
    page_spec = pl.BlockSpec(
        (None, 1, page_size, width),
        lambda b_, ik, ly, pt, kl: (ly[0], pt[b_, ik], 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, g, width), lambda b_, ik, ly, pt, kl: (b_, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, g, width), lambda b_, ik, ly, pt, kl: (b_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g * (width // chunk), chunk, chunk),
                       jnp.promote_types(q.dtype, k_pages.dtype)),
            pltpu.VMEM((g, width), jnp.float32),
            pltpu.VMEM((g, width), jnp.float32),
            pltpu.VMEM((g, width), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, width), q.dtype),
        interpret=interpret,
    )(_layer_operand(layer), page_table.astype(jnp.int32),
      kv_len.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(b, g, hkv, d).swapaxes(1, 2).reshape(b, h, d)


# ---------------------------------------------------------------------------
# paged kv-append: write one token's K/V into its page, in place
# ---------------------------------------------------------------------------


def _kv_append_kernel(
    layer_ref,   # scalar prefetch [1] int32 layer of the stacked pools
    pt_ref,      # scalar prefetch [B, n_pages] int32
    pos_ref,     # scalar prefetch [B] int32 (write position per sequence)
    *refs,       # n new rows [1, 1, W], n target pages [1, page, W]
                 # aliased in/out, then the n output pages
    page_size: int,
):
    del layer_ref, pt_ref  # consumed by the index maps
    ib = pl.program_id(0)
    n = len(refs) // 3
    new, pages, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    # ``input_output_aliases`` is XLA buffer donation, not window
    # initialization: on TPU the Mosaic output windows are write-only and
    # start undefined (interpret mode happens to seed them from the
    # donated input, which is why tests alone cannot catch this).  The
    # whole page block must therefore be written: the co-mapped input
    # page, with the one row this token owns replaced.
    row = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
    mine = row == pos_ref[ib] % page_size
    for new_ref, page_ref, out_ref in zip(new, pages, outs):
        out_ref[0] = jnp.where(mine, new_ref[0], page_ref[0])


def _append_rows(rows, pools, page_table, pos, layer, interpret):
    """Write row ``b`` of each ``rows[i]`` ``[B, 1, W_i]`` into layer
    ``layer`` of pool ``pools[i]`` ``[L, P, page, W_i]`` at sequence b's
    position, in place: the whole stacked pool is aliased, and only the
    one page a sequence writes moves."""
    b = rows[0].shape[0]
    page_size = pools[0].shape[2]
    n_pages = page_table.shape[1]
    n = len(pools)
    kernel = functools.partial(_kv_append_kernel, page_size=page_size)
    # One grid step per sequence; the index map routes both the aliased
    # input block and the output block to the page owning position
    # pos[b], so only that page's row ``pos % page_size`` changes.  The
    # table read is clamped: an idle batcher slot's pos keeps advancing
    # past ``n_pages * page_size`` (empty slots still ride the static-
    # shape decode step), and an OOB scalar read is undefined on TPU —
    # it could resolve to an arbitrary page id and route the idle slot's
    # garbage write into a live request's page.  Clamped, the write
    # lands in the slot's own last table entry (the scratch page 0 for
    # an idle, all-zero table row).
    page_idx = lambda b_, ly, pt, ps: (
        ly[0], pt[b_, jnp.minimum(ps[b_] // page_size, n_pages - 1)], 0, 0
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 1, r.shape[2]),
                               lambda b_, ly, pt, ps: (b_, 0, 0))
                  for r in rows]
        + [pl.BlockSpec((None, 1, page_size, p.shape[3]), page_idx)
           for p in pools],
        out_specs=[pl.BlockSpec((None, 1, page_size, p.shape[3]), page_idx)
                   for p in pools],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # Operand indices count the three scalar-prefetch args, then the
        # n new rows: the pools follow, aliased so the update is in place.
        input_output_aliases={3 + n + i: i for i in range(n)},
        interpret=interpret,
    )(_layer_operand(layer), page_table.astype(jnp.int32),
      pos.astype(jnp.int32), *rows, *pools)


def paged_kv_append_fwd(
    k_new: jax.Array,       # [B, Hkv, D] this tick's keys
    v_new: jax.Array,       # [B, Hkv, D]
    k_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    v_pages: jax.Array,     # [L, P, page_size, Hkv*D]
    page_table: jax.Array,  # [B, n_pages] int32
    pos: jax.Array,         # [B] int32 write positions (== kv_len pre-append)
    layer: jax.Array,       # int32 scalar, the pool's layer to write
    interpret: bool = False,
) -> "tuple[jax.Array, jax.Array]":
    b = k_new.shape[0]
    width = k_pages.shape[3]
    # One lane-dense row per sequence, in the pool's dtype.
    k_row = k_new.reshape(b, 1, width).astype(k_pages.dtype)
    v_row = v_new.reshape(b, 1, width).astype(v_pages.dtype)
    k_pages, v_pages = _append_rows((k_row, v_row), (k_pages, v_pages),
                                    page_table, pos, layer, interpret)
    return k_pages, v_pages


# ---------------------------------------------------------------------------
# paged latent decode (MLA): one latent row per token, every head on it
# ---------------------------------------------------------------------------


def _paged_latent_decode_kernel(
    layer_ref,   # scalar prefetch [1] int32 layer of the stacked pool
    pt_ref,      # scalar prefetch [B, n_blocks * pages_per_block] int32
    kv_len_ref,  # scalar prefetch [B] int32
    q_ref,       # [1, H, W] latent-space queries (absorbed q, rope q, 0)
    *refs,       # pages_per_block pages [1, page, W], then o_ref [1, H, V],
                 # then scratch m, l [H, LANE] f32 and acc [H, V] f32
    sm_scale: float,
    page_size: int,
    pages_per_block: int,
    value_dim: int,
):
    del layer_ref, pt_ref  # consumed by the index maps
    pages = refs[:pages_per_block]
    o_ref, m_ref, l_ref, acc_ref = refs[pages_per_block:]
    ib, ik = pl.program_id(0), pl.program_id(1)
    tokens = page_size * pages_per_block

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[ib]

    # Blocks at or past the live length are skipped (their table entries
    # are the scratch page, fetched once and not again).
    @pl.when(ik * tokens < kv_len)
    def _update():
        # The block's pages stacked row-major: [tokens, W].  Every head
        # scores the whole row (latent and rope lanes at once, the
        # padding lanes zero in q); values are the row's first V lanes.
        rows = jnp.concatenate([p[0] for p in pages], axis=0)
        q = q_ref[0]
        precision = (jax.lax.Precision.HIGHEST
                     if rows.dtype == jnp.float32 else None)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * sm_scale  # [H, tokens]
        k_pos = ik * tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = k_pos < kv_len
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(live, jnp.exp(s - m_cur), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :value_dim],
            preferred_element_type=jnp.float32, precision=precision,
        )
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def latent_pages_per_block(page_size: int) -> int:
    """Pages one grid step of the latent kernel reads: 512 tokens' rows.
    Fewer, larger steps: a step costs about a third of a microsecond
    whatever it holds, and 16 heads over 512 rows is still a small MXU
    pass."""
    return max(1, 512 // page_size)


def paged_latent_decode_fwd(
    q: jax.Array,           # [B, H, W]
    pages: jax.Array,       # [L, P, page_size, W] latent rows
    page_table: jax.Array,  # [B, n_pages] int32
    kv_len: jax.Array,      # [B] int32
    layer: jax.Array,       # int32 scalar, the pool's layer to read
    value_dim: int,
    sm_scale: float,
    pages_per_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, w = q.shape
    page_size = pages.shape[2]
    ppb = pages_per_block or latent_pages_per_block(page_size)
    n_pages = page_table.shape[1]
    ppb = min(ppb, n_pages)
    n_blocks = -(-n_pages // ppb)
    # Unallocated table entries are 0, the scratch page.
    table = jnp.pad(page_table.astype(jnp.int32),
                    ((0, 0), (0, n_blocks * ppb - n_pages)))
    kernel = functools.partial(
        _paged_latent_decode_kernel, sm_scale=sm_scale, page_size=page_size,
        pages_per_block=ppb, value_dim=value_dim,
    )

    def page_spec(j):
        # The page-table gather: page j of block ik of sequence b_ lives
        # in pool page pt[b_, ik * ppb + j] of the layer's pool, resolved
        # at DMA-schedule time.
        return pl.BlockSpec(
            (None, 1, page_size, w),
            lambda b_, ik, ly, pt, kl: (ly[0], pt[b_, ik * ppb + j], 0, 0),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_blocks),
        in_specs=[pl.BlockSpec((1, h, w),
                               lambda b_, ik, ly, pt, kl: (b_, 0, 0))]
        + [page_spec(j) for j in range(ppb)],
        out_specs=pl.BlockSpec((1, h, value_dim),
                               lambda b_, ik, ly, pt, kl: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, LANE), jnp.float32),
            pltpu.VMEM((h, LANE), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        interpret=interpret,
    )(_layer_operand(layer), table, kv_len.astype(jnp.int32), q,
      *([pages] * ppb))


def paged_latent_append_fwd(
    row: jax.Array,         # [B, W] this tick's latent rows
    pages: jax.Array,       # [L, P, page_size, W]
    page_table: jax.Array,  # [B, n_pages] int32
    pos: jax.Array,         # [B] int32 write positions
    layer: jax.Array,       # int32 scalar, the pool's layer to write
    interpret: bool = False,
) -> jax.Array:
    b, w = row.shape
    (pages,) = _append_rows((row.reshape(b, 1, w).astype(pages.dtype),),
                            (pages,), page_table, pos, layer, interpret)
    return pages
