"""Whisper-style encoder-decoder (audio family).

The mel-spectrogram + conv frontend is a STUB per the assignment:
``input_specs`` feeds precomputed frame embeddings (B, encoder_seq, d_model).
Sinusoidal positions on both sides (the real model uses learned decoder
positions capped at 448; the assigned decode shapes reach 32k, so we use
the unbounded sinusoidal form — recorded in DESIGN.md).

Decoder block: self-attn (causal) -> cross-attn (to cached encoder KV) ->
MLP. Encoder: bidirectional self-attn blocks over the frames.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.ctx import shard_act
from . import attention as attn
from .layers import (
    dtype_of, embed_apply, embed_init, logits_apply, mlp_apply, mlp_init,
    norm_apply, norm_init,
)


def sinusoidal(positions: jax.Array, dim: int) -> jax.Array:
    """(…,) int positions -> (…, dim) float32 sinusoidal embeddings."""
    half = dim // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": norm_init(cfg, cfg.d_model),
            "attn": attn.attn_init(k1, cfg),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(k2, cfg, cfg.d_model, cfg.d_ff)}


def _dec_block_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": norm_init(cfg, cfg.d_model),
            "attn": attn.attn_init(k1, cfg),
            "lnx": norm_init(cfg, cfg.d_model),
            "xattn": attn.attn_init(k2, cfg, cross=True),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(k3, cfg, cfg.d_model, cfg.d_ff)}


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kenc, kdec = jax.random.split(key, 3)
    return {
        "tok": embed_init(ke, cfg),
        "enc_layers": jax.vmap(lambda k: _enc_block_init(k, cfg))(
            jax.random.split(kenc, cfg.num_encoder_layers)),
        "enc_norm": norm_init(cfg, cfg.d_model),
        "dec_layers": jax.vmap(lambda k: _dec_block_init(k, cfg))(
            jax.random.split(kdec, cfg.num_layers)),
        "final_norm": norm_init(cfg, cfg.d_model),
    }


def encode(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    """frames: (B, T, d_model) precomputed frontend embeddings."""
    b, t, _ = frames.shape
    x = frames.astype(dtype_of(cfg))
    x = x + sinusoidal(jnp.arange(t), cfg.d_model).astype(x.dtype)[None]
    x = shard_act(x, ("batch", "frames", "embed"))

    def body(h, lp):
        a, _ = attn.self_attention(cfg, lp["attn"],
                                   norm_apply(cfg, lp["ln1"], h),
                                   causal=False)
        h = h + a
        h = h + mlp_apply(cfg, lp["mlp"], norm_apply(cfg, lp["ln2"], h))
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return norm_apply(cfg, params["enc_norm"], x)


def _dec_embed(cfg, params, tokens, offset=0):
    x = embed_apply(cfg, params["tok"], tokens)
    pos = jnp.arange(tokens.shape[1]) + offset
    return x + sinusoidal(pos, cfg.d_model).astype(x.dtype)[None]


def hidden(cfg: ModelConfig, params: dict, batch: dict,
           *, window: int | None = None
           ) -> tuple[jax.Array, jax.Array, dict]:
    """Final-norm decoder hidden states (pre-logits), aux=0, no stats."""
    window = cfg.sliding_window if window is None else window
    enc = encode(cfg, params, batch["frames"])
    x = _dec_embed(cfg, params, batch["tokens"])

    def body(h, lp):
        a, _ = attn.self_attention(cfg, lp["attn"],
                                   norm_apply(cfg, lp["ln1"], h),
                                   causal=True, window=window)
        h = h + a
        kv = attn.cross_kv(cfg, lp["xattn"], enc)
        h = h + attn.cross_attention(cfg, lp["xattn"],
                                     norm_apply(cfg, lp["lnx"], h), kv)
        h = h + mlp_apply(cfg, lp["mlp"], norm_apply(cfg, lp["ln2"], h))
        return h, None

    body = (jax.checkpoint(body) if cfg.remat == "full" else body)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    h = norm_apply(cfg, params["final_norm"], x)
    return h, jnp.zeros((), jnp.float32), {}


def forward(cfg: ModelConfig, params: dict, batch: dict,
            *, window: int | None = None
            ) -> tuple[jax.Array, jax.Array, dict]:
    """batch: {"tokens": (B,S), "frames": (B,T,D)} -> (logits, aux=0,
    no stats)."""
    h, aux, stats = hidden(cfg, params, batch, window=window)
    return logits_apply(cfg, params["tok"], h), aux, stats


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None) -> dict:
    dtype = dtype or dtype_of(cfg)
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    L = cfg.num_layers
    return {
        "index": jnp.zeros((), jnp.int32),
        "pos": jnp.full((cache_len,), -1, jnp.int32),
        "layers": jax.vmap(
            lambda _: attn.cache_init(cfg, batch, cache_len, dtype)
        )(jnp.arange(L)),
        "cross": {"k": jnp.zeros((L, batch, cfg.encoder_seq, kh, hd), dtype),
                  "v": jnp.zeros((L, batch, cfg.encoder_seq, kh, hd), dtype)},
    }


def prefill(cfg: ModelConfig, params: dict, batch: dict,
            *, window: int | None = None,
            cache_len: int | None = None) -> tuple[jax.Array, dict]:
    window = cfg.sliding_window if window is None else window
    enc = encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache_len = max(cache_len or s, s)
    x = _dec_embed(cfg, params, tokens)
    cache = init_cache(cfg, b, cache_len)

    def body(h, lp):
        a, kv = attn.self_attention(cfg, lp["attn"],
                                    norm_apply(cfg, lp["ln1"], h),
                                    causal=True, window=window)
        h = h + a
        ckv = attn.cross_kv(cfg, lp["xattn"], enc)
        h = h + attn.cross_attention(cfg, lp["xattn"],
                                     norm_apply(cfg, lp["lnx"], h), ckv)
        h = h + mlp_apply(cfg, lp["mlp"], norm_apply(cfg, lp["ln2"], h))
        return h, (kv, ckv)

    x, (kvs, ckvs) = jax.lax.scan(body, x, params["dec_layers"])
    from .transformer import _place, _pos_tags
    cache["layers"] = jax.tree.map(lambda t: _place(t, cache_len), kvs)
    cache["cross"] = ckvs
    cache["pos"] = _pos_tags(s, cache_len)
    cache["index"] = jnp.asarray(s, jnp.int32)
    h = norm_apply(cfg, params["final_norm"], x)
    return logits_apply(cfg, params["tok"], h), cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                tokens: jax.Array, *, window: int | None = None
                ) -> tuple[jax.Array, dict]:
    window = cfg.sliding_window if window is None else window
    index = cache["index"]
    pos_tags = cache["pos"]
    x = _dec_embed(cfg, params, tokens, offset=index)

    def body(h, scanned):
        lp, lc, xc = scanned
        a, upd = attn.decode_self_attention(
            cfg, lp["attn"], norm_apply(cfg, lp["ln1"], h), lc, index,
            pos_tags, window=window)
        h = h + a
        h = h + attn.cross_attention(cfg, lp["xattn"],
                                     norm_apply(cfg, lp["lnx"], h), xc)
        h = h + mlp_apply(cfg, lp["mlp"], norm_apply(cfg, lp["ln2"], h))
        return h, upd

    x, upd = jax.lax.scan(body, x, (params["dec_layers"], cache["layers"],
                                    cache["cross"]))
    new_cache = dict(cache)
    new_cache["layers"] = {"k": upd["k"], "v": upd["v"]}
    new_cache["pos"] = upd["pos"][0]
    new_cache["index"] = index + 1
    h = norm_apply(cfg, params["final_norm"], x)
    return logits_apply(cfg, params["tok"], h), new_cache
