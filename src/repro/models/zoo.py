"""Model facade: build any assigned architecture from its ArchConfig and
expose train / prefill / decode entry points plus ShapeDtypeStruct input
specs for the dry-run.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ArchConfig, ShapeSpec
from repro.models import transformer as T
from repro.models.layers import PagedSpec

Params = Dict[str, Any]


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    # -- params / cache -----------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        return T.init_params(rng, self.cfg, dtype=self.param_dtype)

    def init_cache(
        self, batch: int, max_len: int, ring: bool = False,
        paged: Optional[PagedSpec] = None,
    ) -> Params:
        return T.init_cache(self.cfg, batch, max_len, dtype=self.compute_dtype,
                            ring=ring, paged=paged)

    # -- entry points ---------------------------------------------------------
    def train_logits(
        self, params: Params, batch: Dict[str, jax.Array]
    ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence causal logits. Returns (logits, aux_loss)."""
        logits, _, aux = T.forward(
            params,
            self.cfg,
            batch["tokens"],
            frontend=batch.get("frontend"),
            compute_dtype=self.compute_dtype,
        )
        return logits, aux

    def prefill(
        self,
        params: Params,
        batch: Dict[str, jax.Array],
        cache: Params,
        last_only: bool = False,
    ) -> Tuple[jax.Array, Params]:
        logits, cache, _ = T.forward(
            params,
            self.cfg,
            batch["tokens"],
            cache=cache,
            frontend=batch.get("frontend"),
            start_pos=jnp.zeros((batch["tokens"].shape[0],), dtype=jnp.int32),
            compute_dtype=self.compute_dtype,
            logits_positions="last" if last_only else "all",
        )
        return logits, cache

    def decode_step(
        self,
        params: Params,
        tokens: jax.Array,  # [B, 1]
        cache: Params,
        positions: jax.Array,  # [B]
        frontend: Optional[jax.Array] = None,  # enc-dec cross context
    ) -> Tuple[jax.Array, Params]:
        logits, cache, _ = T.forward(
            params,
            self.cfg,
            tokens,
            cache=cache,
            frontend=frontend,
            start_pos=positions,
            compute_dtype=self.compute_dtype,
        )
        return logits, cache

    # -- loss ------------------------------------------------------------------
    def loss_fn(
        self, params: Params, batch: Dict[str, jax.Array]
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.train_logits(params, batch)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux, "tokens": denom}


def build_model(
    cfg: ArchConfig,
    compute_dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
) -> Model:
    return Model(cfg=cfg, compute_dtype=compute_dtype, param_dtype=param_dtype)


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def input_specs(
    cfg: ArchConfig, shape: ShapeSpec, compute_dtype=jnp.bfloat16
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Stand-ins for every model input of this (arch, shape) cell."""
    b = shape.global_batch
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        specs: Dict[str, Any] = {
            "tokens": sds((b, shape.seq_len), jnp.int32),
            "labels": sds((b, shape.seq_len), jnp.int32),
        }
        if cfg.encoder_layers > 0:
            specs["frontend"] = sds((b, cfg.encoder_seq, cfg.d_model), compute_dtype)
        elif cfg.frontend_tokens > 0:
            specs["frontend"] = sds(
                (b, cfg.frontend_tokens, cfg.d_model), compute_dtype
            )
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": sds((b, shape.seq_len), jnp.int32)}
        if cfg.encoder_layers > 0:
            specs["frontend"] = sds((b, cfg.encoder_seq, cfg.d_model), compute_dtype)
        elif cfg.frontend_tokens > 0:
            specs["frontend"] = sds(
                (b, cfg.frontend_tokens, cfg.d_model), compute_dtype
            )
        return specs
    # decode: one new token against a cache of shape.seq_len
    specs = {
        "tokens": sds((b, 1), jnp.int32),
        "positions": sds((b,), jnp.int32),
    }
    if cfg.encoder_layers > 0:
        specs["frontend"] = sds((b, cfg.encoder_seq, cfg.d_model), compute_dtype)
    return specs
