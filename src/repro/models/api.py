"""Uniform model API: ``build_model(cfg)`` -> Model(init/forward/loss/
prefill/decode_step/init_cache/input_specs).

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of the given input-shape config — weak-type-correct, shardable,
zero allocation — used by the multi-pod dry-run and by ``jax.eval_shape``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import encdec, transformer

# dense archs use this ring-buffer window for the long_500k decode shape
# (the explicitly-implemented sub-quadratic sliding-window variant).
LONG_CONTEXT_WINDOW = 8192


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    forward: Callable[..., tuple[jax.Array, jax.Array, dict]]
    hidden: Callable[..., tuple[jax.Array, jax.Array, dict]]
    prefill: Callable[..., tuple[jax.Array, Any]]
    decode_step: Callable[..., tuple[jax.Array, Any]]
    init_cache: Callable[..., Any]

    def loss(self, params, batch, *, window: int | None = None):
        logits, aux, _ = self.forward(params, batch, window=window)
        tokens = batch["tokens"]
        loss = transformer.lm_loss(self.cfg, logits, tokens,
                                   batch.get("loss_weights"))
        return loss + self.cfg.router_aux_weight * aux, logits


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec":
        mod = encdec
    elif cfg.family == "cnn":
        raise ValueError("use repro.models.cnn directly for the paper CNN")
    else:
        mod = transformer
    return Model(
        cfg=cfg,
        init=lambda key: mod.init(key, cfg),
        forward=lambda params, batch, **kw: mod.forward(
            cfg, params, batch, **kw),
        hidden=lambda params, batch, **kw: mod.hidden(
            cfg, params, batch, **kw),
        prefill=lambda params, batch, **kw: mod.prefill(
            cfg, params, batch, **kw),
        decode_step=lambda params, cache, tokens, **kw: mod.decode_step(
            cfg, params, cache, tokens, **kw),
        init_cache=lambda batch, cache_len, dtype=None: mod.init_cache(
            cfg, batch, cache_len, dtype),
    )


def decode_window(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Sliding window used for a decode shape (0 = full attention)."""
    if shape.name == "long_500k" and cfg.family != "ssm":
        return LONG_CONTEXT_WINDOW
    return cfg.sliding_window


def attn_cache_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """KV-cache length for decode: ring buffer when windowed."""
    w = decode_window(cfg, shape)
    return min(shape.seq_len, w) if w else shape.seq_len


def supported(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Is (arch x shape) in scope? (the one documented skip)."""
    if cfg.family == "encdec" and shape.name == "long_500k":
        return False, ("whisper context is bounded by construction "
                       "(1500 frames / 448-token decoder); 500k-token "
                       "decode has no analogue — documented skip")
    if cfg.family == "cnn":
        return False, "paper CNN is exercised by the FL simulator, not LM shapes"
    return True, ""


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every input of (arch, shape)."""
    b, s = shape.global_batch, shape.seq_len
    tok = jnp.int32
    act = jnp.dtype(cfg.dtype)
    i32 = jnp.int32

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt)

    if shape.kind in ("train", "prefill"):
        text = s
        specs: dict[str, Any] = {}
        if cfg.family == "vlm":
            text = s - cfg.num_patches
            specs["patches"] = sds((b, cfg.num_patches, cfg.d_model), act)
        if cfg.family == "encdec":
            specs["frames"] = sds((b, cfg.encoder_seq, cfg.d_model), act)
        specs["tokens"] = sds((b, text), tok)
        return specs

    # decode: one new token + a full cache of seq_len context
    cache_len = attn_cache_len(cfg, shape)
    model = build_model(cfg)
    cache = jax.eval_shape(
        lambda: model.init_cache(b, cache_len))
    return {"tokens": sds((b, 1), tok), "cache": cache}
