"""Unified decoder (+ optional encoder) model over ArchConfig.

Depth is executed as a **period scan**: the config's layer ``pattern``
(e.g. gemma3's 5 local + 1 global, jamba's 8-sublayer period) defines one
scan body; parameters are stacked ``[n_periods, ...]`` per pattern
position, and ``num_layers % len(pattern)`` remainder layers run
unrolled.  This keeps the HLO O(pattern) instead of O(depth) — compile
times and program size stay flat from 2 layers to 64 (critical for the
512-device dry-run on one CPU).  The config's ``lead`` layers (DeepSeek's
leading dense layer) run unrolled before the scan, with their own params
and caches under ``"lead"``.

Caches (attention KV / mamba conv+ssm states) are pytrees with the same
period structure, threaded through the scan as (xs -> ys), except the
page pools (every ``*_pages`` leaf): those ride in the scan's carry,
stacked ``[n_periods, P, page, W]``, and each layer updates its own
layer of them in place, so no layer's pool is sliced out and written
back (see ``_split_pools``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ArchConfig, AttentionKind, FFNKind, LayerSpec
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# one block (a single pattern position)
# ---------------------------------------------------------------------------


def init_block(rng: jax.Array, cfg: ArchConfig, spec: LayerSpec, dtype) -> Params:
    ks = jax.random.split(rng, 4)
    p: Params = {"norm_attn": jnp.zeros((cfg.d_model,), dtype=dtype)}
    if spec.is_mamba:
        p["mamba"] = M.init_mamba(ks[0], cfg, dtype)
    elif spec.attention == AttentionKind.LATENT:
        p["attn"] = L.init_latent_attention(ks[0], cfg, dtype)
    elif spec.attention != AttentionKind.NONE:
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
        if spec.attention == AttentionKind.CROSS:
            p["cross"] = L.init_attention(ks[3], cfg, dtype)
            p["norm_cross"] = jnp.zeros((cfg.d_model,), dtype=dtype)
    if spec.ffn != FFNKind.NONE:
        p["norm_ffn"] = jnp.zeros((cfg.d_model,), dtype=dtype)
        if spec.ffn == FFNKind.MOE:
            p["moe"] = MOE.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg, dtype)
    return p


def init_block_cache(
    cfg: ArchConfig, spec: LayerSpec, batch: int, max_len: int, dtype,
    ring: bool = False, paged: Optional[L.PagedSpec] = None,
) -> Optional[Params]:
    """Cache entry for one block (None if the block is stateless).

    ``ring=True``: sliding-window layers get a window-sized ring buffer
    instead of a max_len linear cache — at 512k context with W=1024 this
    is a 512x cache-memory reduction for every local layer (global
    layers keep the full cache; absolute-position masking makes the two
    interoperate).

    ``paged``: every attention layer stores K/V in a shared page pool
    behind per-slot page tables (serving hot path; overrides ``ring``).
    The same table values index every layer's pool, so the serving
    ``PagePool`` does its accounting once per slot, not per layer."""
    if spec.is_mamba:
        return {"mamba": M.init_mamba_cache(cfg, batch, dtype)}
    if spec.attention == AttentionKind.LATENT:
        return {"attn": L.init_latent_cache(cfg, batch, max_len, dtype,
                                            paged=paged)}
    if spec.attention != AttentionKind.NONE:
        if paged is not None and spec.attention != AttentionKind.CROSS:
            return {"attn": L.init_attention_cache(
                cfg, batch, max_len, dtype, paged=paged)}
        ring_window = 0
        if ring and spec.attention == AttentionKind.SLIDING and spec.window > 0:
            # round up to a multiple of 16 so the seq dim stays shardable
            ring_window = ((spec.window + 15) // 16) * 16
        return {"attn": L.init_attention_cache(
            cfg, batch, max_len, dtype, ring_window=ring_window)}
    return None


def apply_block(
    params: Params,
    spec: LayerSpec,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    cache: Optional[Params],
    enc_out: Optional[jax.Array],
) -> Tuple[jax.Array, Optional[Params], jax.Array, jax.Array]:
    """Returns (x', cache', aux_loss, held), ``held`` [B] the (token,
    expert) assignments that landed on this chip's held experts."""
    aux = jnp.zeros((), dtype=jnp.float32)
    held = jnp.zeros((x.shape[0],), dtype=jnp.int32)
    new_cache: Optional[Params] = None
    rs = cfg.residual_scale

    if spec.is_mamba:
        h = L.rms_norm(x, params["norm_attn"], cfg.norm_eps)
        y, mc = M.mamba_block(
            params["mamba"], h, cfg,
            cache=cache.get("mamba") if cache else None,
        )
        x = x + rs * y
        new_cache = {"mamba": mc} if mc is not None else None
    elif spec.attention == AttentionKind.LATENT:
        h = L.rms_norm(x, params["norm_attn"], cfg.norm_eps)
        y, ac = L.latent_attention(
            params["attn"], h, positions, cfg,
            cache=cache.get("attn") if cache else None,
        )
        x = x + rs * y
        new_cache = {"attn": ac} if ac is not None else None
    elif spec.attention != AttentionKind.NONE:
        h = L.rms_norm(x, params["norm_attn"], cfg.norm_eps)
        attn_cache = cache.get("attn") if cache else None
        self_spec = (
            LayerSpec(attention=AttentionKind.FULL, ffn=spec.ffn)
            if spec.attention == AttentionKind.CROSS
            else spec
        )
        y, ac = L.attention(
            params["attn"], h, positions, cfg, self_spec,
            cache=attn_cache,
        )
        if cfg.parallel_block:
            # command-r style: attn and FFN both read the same normed input.
            y2 = L.mlp(params["mlp"], h)
            x = x + rs * (y + y2)
            new_cache = {"attn": ac} if ac is not None else None
            return shard(x, "batch", "seq", "embed"), new_cache, aux, held
        x = x + rs * y
        new_cache = {"attn": ac} if ac is not None else None
        if spec.attention == AttentionKind.CROSS and enc_out is not None:
            h = L.rms_norm(x, params["norm_cross"], cfg.norm_eps)
            y, _ = L.attention(
                params["cross"], h, positions, cfg, spec,
                kv_x=enc_out,
            )
            x = x + rs * y

    if spec.ffn != FFNKind.NONE:
        h = L.rms_norm(x, params["norm_ffn"], cfg.norm_eps)
        if spec.ffn == FFNKind.MOE and cfg.moe.held_experts:
            y, moe_aux, held = MOE.moe_share(params["moe"], h, cfg.moe)
            aux = aux + moe_aux
        elif spec.ffn == FFNKind.MOE:
            y, moe_aux = MOE.moe_apply(params["moe"], h, cfg.moe)
            aux = aux + moe_aux
        else:
            y = L.mlp(params["mlp"], h)
        x = x + rs * y

    return shard(x, "batch", "seq", "embed"), new_cache, aux, held


# ---------------------------------------------------------------------------
# the full model
# ---------------------------------------------------------------------------


def _is_pool_key(key: Any) -> bool:
    """A page pool's key in a cache: ``k_pages``, ``v_pages``,
    ``latent_pages``."""
    return str(key).endswith("_pages")


def _split_pools(tree: Any) -> Tuple[List[Any], List[Any], Any, List[bool]]:
    """``(pools, rest, treedef, is_pool)``: the tree's page-pool leaves
    apart from its other leaves, and what ``_join_pools`` needs to put
    them back."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    is_pool = [isinstance(path[-1], jax.tree_util.DictKey)
               and _is_pool_key(path[-1].key) for path, _ in flat]
    pools = [leaf for (_, leaf), m in zip(flat, is_pool) if m]
    rest = [leaf for (_, leaf), m in zip(flat, is_pool) if not m]
    return pools, rest, treedef, is_pool


def _join_pools(treedef: Any, is_pool: List[bool], pools: List[Any],
                rest: List[Any]) -> Any:
    it_pools, it_rest = iter(pools), iter(rest)
    return treedef.unflatten(
        [next(it_pools) if m else next(it_rest) for m in is_pool])


def _at_layer(tree: Any, layer: jax.Array) -> Any:
    """The cache tree with ``"layer": layer`` beside every page pool: the
    index into the stacked pool that the paged attention layers take."""
    if isinstance(tree, dict):
        out = {k: _at_layer(v, layer) for k, v in tree.items()}
        if any(_is_pool_key(k) for k in tree):
            out["layer"] = layer
        return out
    if isinstance(tree, (list, tuple)):
        return type(tree)(_at_layer(v, layer) for v in tree)
    return tree


def _period_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """Scanned periods and unrolled remainder layers after the lead."""
    plen, rest = len(cfg.pattern), cfg.num_layers - len(cfg.lead)
    return rest // plen, rest % plen


def _counts_experts(cfg: ArchConfig) -> bool:
    return cfg.moe is not None and cfg.moe.held_experts > 0


def init_params(rng: jax.Array, cfg: ArchConfig, dtype=jnp.float32) -> Params:
    n_periods, remainder = _period_counts(cfg)
    keys = jax.random.split(rng, 8)
    params: Params = {"embed": L.init_embedding(keys[0], cfg, dtype)}
    if cfg.lead:
        params["lead"] = [
            init_block(jax.random.fold_in(keys[4], i), cfg, spec, dtype)
            for i, spec in enumerate(cfg.lead)
        ]

    # Stacked params per pattern position: [n_periods, ...]
    if n_periods > 0:
        period_params: List[Params] = []
        for pos, spec in enumerate(cfg.pattern):
            def init_one(r):
                return init_block(r, cfg, spec, dtype)

            ks = jax.random.split(jax.random.fold_in(keys[1], pos), n_periods)
            # vmap, not a Python loop + stack: one batched init per
            # pattern position keeps a jitted init's program O(pattern)
            period_params.append(jax.vmap(init_one)(ks))
        params["periods"] = period_params
    if remainder > 0:
        params["remainder"] = [
            init_block(
                jax.random.fold_in(keys[2], i),
                cfg,
                cfg.layer_spec(len(cfg.lead) + n_periods * len(cfg.pattern) + i),
                dtype,
            )
            for i in range(remainder)
        ]
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype=dtype)

    if cfg.encoder_layers > 0:
        enc_spec = LayerSpec(attention=AttentionKind.FULL, ffn=FFNKind.DENSE)
        ks = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = jax.tree.map(
            lambda *ls: jnp.stack(ls),
            *[init_block(k, cfg, enc_spec, dtype) for k in ks],
        )
        params["encoder_norm"] = jnp.zeros((cfg.d_model,), dtype=dtype)
    return params


def init_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
    ring: bool = False, paged: Optional[L.PagedSpec] = None,
) -> Params:
    n_periods, remainder = _period_counts(cfg)
    cache: Params = {}
    if cfg.lead:
        cache["lead"] = [
            init_block_cache(cfg, spec, batch, max_len, dtype, ring=ring,
                             paged=paged)
            for spec in cfg.lead
        ]
    if _counts_experts(cfg):
        # Per row, the (token, held expert) assignments of its last step.
        cache["expert_rows"] = jnp.zeros((batch,), dtype=jnp.int32)
    if n_periods > 0:
        period_caches = []
        for pos, spec in enumerate(cfg.pattern):
            one = init_block_cache(cfg, spec, batch, max_len, dtype, ring=ring,
                                   paged=paged)
            if one is None:
                period_caches.append(None)
            else:
                period_caches.append(
                    jax.tree.map(
                        lambda leaf: jnp.broadcast_to(
                            leaf[None], (n_periods,) + leaf.shape
                        ).copy(),
                        one,
                    )
                )
        cache["periods"] = period_caches
    if remainder > 0:
        cache["remainder"] = [
            init_block_cache(
                cfg,
                cfg.layer_spec(len(cfg.lead) + n_periods * len(cfg.pattern) + i),
                batch,
                max_len,
                dtype,
                ring=ring,
                paged=paged,
            )
            for i in range(remainder)
        ]
    return cache


def _encode(params: Params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over stubbed frame embeddings [B, S_enc, D]."""
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    enc_spec = LayerSpec(attention=AttentionKind.CROSS, ffn=FFNKind.DENSE)
    # CROSS spec with kv_x=self gives non-causal self-attention. The conv
    # frontend is stubbed, so inject sinusoidal positions here.
    d = frames.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    pos_emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    x = frames + pos_emb[..., :d].astype(frames.dtype)

    def body(x, layer_params):
        h = L.rms_norm(x, layer_params["norm_attn"], cfg.norm_eps)
        y, _ = L.attention(
            layer_params["attn"], h, positions, cfg, enc_spec,
            kv_x=h,
        )
        x = x + y
        h = L.rms_norm(x, layer_params["norm_ffn"], cfg.norm_eps)
        x = x + L.mlp(layer_params["mlp"], h)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["encoder_norm"], cfg.norm_eps)


def forward(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,  # [B, T]
    cache: Optional[Params] = None,
    frontend: Optional[jax.Array] = None,  # [B, F, D] patch/frame embeds
    start_pos: Optional[jax.Array] = None,  # [B] decode positions
    compute_dtype=jnp.bfloat16,
    logits_positions: str = "all",  # "all" | "last"
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (logits [B, T_text, V], cache', aux_loss).

    Training/prefill: cache=None/fresh, full sequence.
    Decode: T==1 with a populated cache and start_pos.
    ``logits_positions="last"`` unembeds only the final position — the
    serving-prefill path. This is not a micro-optimization: unembedding
    (and replicating) 32k positions x a 100k+ vocab would be the largest
    tensor and the largest collective of a long prefill.
    """
    n_periods, remainder = _period_counts(cfg)
    b, t = tokens.shape

    x = L.embed(params["embed"], tokens, cfg).astype(compute_dtype)

    enc_out = None
    n_front = 0
    if cfg.encoder_layers > 0 and frontend is not None:
        enc_out = _encode(params, cfg, frontend.astype(compute_dtype))
    elif frontend is not None and cfg.frontend_tokens > 0 and cache is None:
        # VLM: prepend patch embeddings as prefix tokens (train/prefill only;
        # during decode they already live in the cache).
        x = jnp.concatenate([frontend.astype(compute_dtype), x], axis=1)
        n_front = frontend.shape[1]

    t_total = x.shape[1]
    if start_pos is None:
        positions = jnp.broadcast_to(
            jnp.arange(t_total, dtype=jnp.int32)[None], (b, t_total)
        )
    else:
        positions = start_pos[:, None] + jnp.arange(t_total, dtype=jnp.int32)[None]

    aux_total = jnp.zeros((), dtype=jnp.float32)
    held_total = jnp.zeros((b,), dtype=jnp.int32)

    # --- leading layers (unrolled) ------------------------------------------
    new_lead = []
    for i, spec in enumerate(cfg.lead):
        x, nc, a, hd = apply_block(
            params["lead"][i], spec, x, positions, cfg,
            cache["lead"][i] if cache is not None else None, enc_out,
        )
        aux_total, held_total = aux_total + a, held_total + hd
        new_lead.append(nc)

    # --- scanned periods --------------------------------------------------
    if n_periods > 0:
        period_params = params["periods"]
        period_caches = (
            cache["periods"] if cache is not None else [None] * len(cfg.pattern)
        )

        if cache is not None:
            # The stacked pools ride in the carry, whole; the per-layer
            # leaves (page tables, positions, dense and ring caches) are
            # sliced per layer as xs and stacked back as ys.
            pools, rest, treedef, is_pool = _split_pools(tuple(period_caches))

            def body2(carry, xs):
                x, aux, held, pools = carry
                layer_ps, rest, layer = xs
                layer_cs = _at_layer(
                    _join_pools(treedef, is_pool, pools, rest), layer)
                new_cs: List[Any] = []
                for pos, spec in enumerate(cfg.pattern):
                    x, nc, a, hd = apply_block(
                        layer_ps[pos], spec, x, positions, cfg,
                        layer_cs[pos], enc_out,
                    )
                    aux, held = aux + a, held + hd
                    new_cs.append(nc)
                new_pools, new_rest, _, _ = _split_pools(tuple(new_cs))
                return (x, aux, held, new_pools), new_rest

            (x, aux_total, held_total, pools), rest = jax.lax.scan(
                body2, (x, aux_total, held_total, pools),
                (tuple(period_params), rest,
                 jnp.arange(n_periods, dtype=jnp.int32)),
            )
            new_period_caches = _join_pools(treedef, is_pool, pools, rest)
        else:
            def body_nocache(carry, layer_ps):
                x, aux = carry
                new_cs: List[Any] = []
                for pos, spec in enumerate(cfg.pattern):
                    x, _, a, _ = apply_block(
                        layer_ps[pos], spec, x, positions, cfg,
                        None, enc_out,
                    )
                    aux = aux + a
                return (x, aux), None

            (x, aux_total), _ = jax.lax.scan(
                body_nocache, (x, aux_total), tuple(period_params)
            )
            new_period_caches = None

    # --- remainder layers (unrolled) ---------------------------------------
    new_remainder = []
    if remainder > 0:
        rem_caches = (
            cache["remainder"] if cache is not None else [None] * remainder
        )
        base = len(cfg.lead) + n_periods * len(cfg.pattern)
        for i in range(remainder):
            spec = cfg.layer_spec(base + i)
            x, nc, a, hd = apply_block(
                params["remainder"][i], spec, x, positions, cfg,
                rem_caches[i], enc_out,
            )
            aux_total, held_total = aux_total + a, held_total + hd
            new_remainder.append(nc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_front > 0:
        x = x[:, n_front:, :]  # logits over text positions only (VLM)
    if logits_positions == "last":
        x = x[:, -1:, :]
    logits = L.unembed(params["embed"], x, cfg)

    new_cache: Optional[Params] = None
    if cache is not None:
        new_cache = {}
        if cfg.lead:
            new_cache["lead"] = new_lead
        if _counts_experts(cfg):
            new_cache["expert_rows"] = held_total
        if n_periods > 0:
            new_cache["periods"] = list(new_period_caches)
        if remainder > 0:
            new_cache["remainder"] = new_remainder
    return logits, new_cache, aux_total
