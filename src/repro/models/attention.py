"""Grouped-query attention with qk-norm, RoPE variants, sliding windows and
a position-tagged KV cache (full-length or ring-buffer).

Cache layout per layer: {"k": (B, L, K, hd), "v": (B, L, K, hd)}; latent
attention (MLA, ``cfg.kv_lora_rank`` > 0) caches its latent instead:
{"c_kv": (B, L, kv_lora_rank), "k_pe": (B, L, qk_rope_head_dim)}.
The model-level cache additionally carries {"index": (), "pos": (L,)} where
``pos[slot]`` is the global position stored in that slot (-1 = empty). A
ring buffer (L == window < seq_len) makes long_500k decode O(window) for
dense architectures — the sub-quadratic variant required by the assignment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding.ctx import shard_act
from .layers import apply_rope, dense_apply, dense_init, pdtype_of, rms_norm


def attn_init(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "w_q": dense_init(ks[0], cfg, d, h * hd, bias=cfg.attn_bias),
        "w_k": dense_init(ks[1], cfg, d, kh * hd, bias=cfg.attn_bias),
        "w_v": dense_init(ks[2], cfg, d, kh * hd, bias=cfg.attn_bias),
        "w_o": dense_init(ks[3], cfg, h * hd, d),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), pdtype_of(cfg))
        p["k_norm"] = jnp.ones((hd,), pdtype_of(cfg))
    return p


def _project_q(cfg, p, x):
    b, s, _ = x.shape
    q = dense_apply(p["w_q"], x).reshape(b, s, cfg.num_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    return q


def _project_kv(cfg, p, x):
    b, s, _ = x.shape
    k = dense_apply(p["w_k"], x).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = dense_apply(p["w_v"], x).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if "k_norm" in p:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def self_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,                      # (B, S, D)
    *,
    causal: bool = True,
    window: int = 0,
    positions: jax.Array | None = None,   # (B, S) global positions
) -> tuple[jax.Array, dict]:
    """Full-sequence self attention (train / prefill). Returns (out, kv)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_style)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_style)
    q = shard_act(q, ("batch", "seq", "heads", None))
    k = shard_act(k, ("batch", "seq", "kv_heads", None))
    v = shard_act(v, ("batch", "seq", "kv_heads", None))
    out = ops.attention(q, k, v, causal=causal, window=window)
    out = shard_act(out, ("batch", "seq", "heads", None))
    out = dense_apply(p["w_o"], out.reshape(b, s, -1))
    return shard_act(out, ("batch", "seq", "embed")), {"k": k, "v": v}


def cache_init(cfg: ModelConfig, batch: int, cache_len: int,
               dtype) -> dict:
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, cache_len, kh, hd), dtype),
            "v": jnp.zeros((batch, cache_len, kh, hd), dtype)}


def decode_self_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,             # (B, 1, D)
    kv_cache: dict,           # this layer's {"k","v"} (B, L, K, hd)
    index: jax.Array,         # ()  global decode position
    pos_tags: jax.Array,      # (L,) global position per slot (-1 empty)
    *,
    window: int = 0,
) -> tuple[jax.Array, dict]:
    """One decode step; writes slot index % L (ring when L < seq_len)."""
    b = x.shape[0]
    L = kv_cache["k"].shape[1]
    positions = jnp.broadcast_to(index[None, None], (b, 1))
    q = _project_q(cfg, p, x)
    k_new, v_new = _project_kv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_style)
    k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.rope_style)

    slot = jnp.mod(index, L)
    k = jax.lax.dynamic_update_slice(
        kv_cache["k"], k_new.astype(kv_cache["k"].dtype),
        (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(
        kv_cache["v"], v_new.astype(kv_cache["v"].dtype),
        (0, slot, 0, 0))
    tags = pos_tags.at[slot].set(index)
    out = ops.attention(
        q, k, v, causal=True, window=window, q_offset=positions[:, :1],
        kv_positions=jnp.broadcast_to(tags[None], (b, L)))
    out = dense_apply(p["w_o"], out.reshape(b, 1, -1))
    return out, {"k": k, "v": v, "pos": tags}


def cross_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,                 # (B, S, D) decoder states
    enc_kv: dict,                 # {"k","v"}: (B, T, K, hd) cached encoder KV
) -> jax.Array:
    b, s, _ = x.shape
    q = _project_q(cfg, p, x)     # no rope on cross attention (whisper)
    out = ops.attention(q, enc_kv["k"], enc_kv["v"], causal=False)
    return dense_apply(p["w_o"], out.reshape(b, s, -1))


def cross_kv(cfg: ModelConfig, p: dict, enc_out: jax.Array) -> dict:
    k, v = _project_kv(cfg, p, enc_out)
    return {"k": k, "v": v}


# ------------------------------------------------------------------ MLA
# DeepSeek-V3 multi-head latent attention with q_lora_rank null. Per head
# q = x W_q -> [q_nope, q_pe]; [c, k_pe] = x W_kva, one k_pe for all heads;
# c = RMSNorm(c); [k_nope, v] = c W_kvb; RoPE on q_pe and k_pe; scores
# [q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope); out W_o.


def mla_init(key, cfg: ModelConfig) -> dict:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {"w_q": dense_init(ks[0], cfg, d, h * (nope + rope)),
            "w_kva": dense_init(ks[1], cfg, d, r + rope),
            "kv_norm": jnp.ones((r,), pdtype_of(cfg)),
            "w_kvb": dense_init(ks[2], cfg, r, h * (nope + vd)),
            "w_o": dense_init(ks[3], cfg, h * vd, d)}


def _mla_q(cfg, p, x, positions):
    b, s, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = dense_apply(p["w_q"], x).reshape(
        b, s, cfg.num_heads, nope + cfg.qk_rope_head_dim)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    return jnp.concatenate([q[..., :nope], q_pe], axis=-1)


def _mla_latent(cfg, p, x, positions):
    """x (B, S, D) -> (c (B, S, r) normed, k_pe (B, S, rope) rotated)."""
    r = cfg.kv_lora_rank
    ckv = dense_apply(p["w_kva"], x)
    c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)
    return c, k_pe[:, :, 0]


def _mla_kv(cfg, p, c, k_pe):
    """Latent (B, T, r), (B, T, rope) -> k (B, T, H, nope + rope), v."""
    b, t, _ = c.shape
    h, nope = cfg.num_heads, cfg.qk_nope_head_dim
    kv = dense_apply(p["w_kvb"], c).reshape(b, t, h, nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(k_pe[:, :, None], (b, t, h, k_pe.shape[-1]))
    return jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]


def mla_attention(cfg: ModelConfig, p: dict, x: jax.Array, *,
                  causal: bool = True, window: int = 0,
                  positions: jax.Array | None = None
                  ) -> tuple[jax.Array, dict]:
    """Full-sequence latent attention. Returns (out, latent cache)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q = shard_act(_mla_q(cfg, p, x, positions),
                  ("batch", "seq", "heads", None))
    c, k_pe = _mla_latent(cfg, p, x, positions)
    k, v = _mla_kv(cfg, p, c, k_pe)
    out = ops.attention(q, k, v, causal=causal, window=window)
    out = dense_apply(p["w_o"], out.reshape(b, s, -1))
    return shard_act(out, ("batch", "seq", "embed")), {"c_kv": c,
                                                       "k_pe": k_pe}


def mla_cache_init(cfg: ModelConfig, batch: int, cache_len: int,
                   dtype) -> dict:
    return {"c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim),
                              dtype)}


def mla_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
               index: jax.Array, pos_tags: jax.Array, *, window: int = 0
               ) -> tuple[jax.Array, dict]:
    """One decode step through the latent cache (slot index % L); the
    cached latents are expanded to every head's keys and values."""
    b = x.shape[0]
    L = cache["c_kv"].shape[1]
    positions = jnp.broadcast_to(index[None, None], (b, 1))
    q = _mla_q(cfg, p, x, positions)
    c_new, kpe_new = _mla_latent(cfg, p, x, positions)
    slot = jnp.mod(index, L)
    c = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, slot, 0))
    k_pe = jax.lax.dynamic_update_slice(
        cache["k_pe"], kpe_new.astype(cache["k_pe"].dtype), (0, slot, 0))
    tags = pos_tags.at[slot].set(index)
    k, v = _mla_kv(cfg, p, c, k_pe)
    out = ops.attention(
        q, k, v, causal=True, window=window, q_offset=positions[:, :1],
        kv_positions=jnp.broadcast_to(tags[None], (b, L)))
    out = dense_apply(p["w_o"], out.reshape(b, 1, -1))
    return out, {"c_kv": c, "k_pe": k_pe, "pos": tags}
