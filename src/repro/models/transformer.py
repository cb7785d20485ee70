"""Decoder-only LM assembled from blocks, scan-over-layers.

Families:
  dense  — [norm->attn, norm->mlp] x L
  moe    — [norm->attn, norm->moe] x L, after ``first_dense_layers``
           [norm->attn, norm->mlp] layers (their own stack, scanned first)
  ssm    — [norm->mamba2] x L
  hybrid — groups of (attn_every-1) ssm blocks + 1 SHARED attention block
           (zamba2): outer scan over groups, inner scan over the ssm stack;
           the shared block's weights live once, its KV cache per group.

Layer params are stacked on a leading axis and consumed by ``lax.scan`` so
HLO size / compile time are depth-independent (94-layer models compile on
the CPU host). ``cfg.remat`` wraps the block body in ``jax.checkpoint``.
Attention is latent (MLA) where ``cfg.kv_lora_rank`` > 0. A held-expert
layer (``cfg.experts_held``) reports the assignments each held expert
computed in the stats that ``backbone``/``hidden``/``forward`` return,
as ``{"expert_rows": (MoE layers, experts_held) int32}``; other models'
stats are empty.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (
    dtype_of, embed_apply, embed_init, logits_apply, mlp_apply, mlp_init,
    norm_apply, norm_init,
)

# --------------------------------------------------------------- block defs


def _block_kind(cfg: ModelConfig) -> str:
    return {"dense": "dense", "vlm": "dense", "moe": "moe",
            "ssm": "ssm"}[cfg.family] if cfg.family != "hybrid" else "hybrid"


def _attn_block_init(key, cfg, moe=True):
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_init(cfg, cfg.d_model),
         "attn": (attn.mla_init(k1, cfg) if cfg.kv_lora_rank
                  else attn.attn_init(k1, cfg)),
         "ln2": norm_init(cfg, cfg.d_model)}
    if cfg.family == "moe" and moe:
        p["moe"] = moe_mod.moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k2, cfg, cfg.d_model, cfg.d_ff)
    return p


# the stacks of attention blocks, in the order a pass runs them
STACKS = ("dense_layers", "layers")


def _stacks(tree: dict) -> list[str]:
    return [name for name in STACKS if name in tree]


def _ssm_block_init(key, cfg):
    return {"ln1": norm_init(cfg, cfg.d_model),
            "ssm": ssm_mod.ssm_init(key, cfg)}


def _stacked(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kl, ka = jax.random.split(key, 3)
    params: dict[str, Any] = {"tok": embed_init(ke, cfg),
                              "final_norm": norm_init(cfg, cfg.d_model)}
    kind = _block_kind(cfg)
    if kind in ("dense", "moe"):
        n_dense = cfg.first_dense_layers
        if n_dense:
            params["dense_layers"] = _stacked(
                lambda k: _attn_block_init(k, cfg, moe=False),
                jax.random.fold_in(kl, 1), n_dense)
        params["layers"] = _stacked(
            lambda k: _attn_block_init(k, cfg), kl, cfg.num_layers - n_dense)
    elif kind == "ssm":
        params["layers"] = _stacked(
            lambda k: _ssm_block_init(k, cfg), kl, cfg.num_layers)
    else:  # hybrid
        groups, per = _hybrid_shape(cfg)
        params["ssm_layers"] = jax.vmap(
            lambda k: _stacked(lambda kk: _ssm_block_init(kk, cfg), k, per)
        )(jax.random.split(kl, groups))
        params["shared_attn"] = _attn_block_init(ka, cfg)
    if cfg.family == "vlm":
        kp = jax.random.fold_in(key, 7)
        params["patch_proj"] = {
            "w": (jax.random.normal(kp, (cfg.d_model, cfg.d_model)) *
                  cfg.d_model ** -0.5).astype(jnp.dtype(cfg.param_dtype))}
    return params


def _hybrid_shape(cfg: ModelConfig) -> tuple[int, int]:
    per = cfg.attn_every - 1                       # ssm blocks per group
    groups = cfg.num_layers // cfg.attn_every
    return groups, per


# --------------------------------------------------------------- full pass


def _self_attention(cfg, p, x, **kw):
    if "w_kva" in p:
        return attn.mla_attention(cfg, p, x, **kw)
    return attn.self_attention(cfg, p, x, **kw)


def _ffn(cfg, p, x):
    """The block's MLP or expert layer on normed ``x``: (out, aux, rows);
    ``rows`` is None but for a held-expert layer."""
    if "mlp" in p:
        return mlp_apply(cfg, p["mlp"], x), 0.0, None
    if cfg.experts_held:
        out, rows = moe_mod.moe_held(cfg, p["moe"], x)
        return out, 0.0, rows
    out, aux = moe_mod.moe_block(cfg, p["moe"], x)
    return out, aux, None


def _attn_block(cfg, p, x, *, window):
    h, _ = _self_attention(cfg, p["attn"], norm_apply(cfg, p["ln1"], x),
                           causal=True, window=window)
    x = x + h
    h, aux, rows = _ffn(cfg, p, norm_apply(cfg, p["ln2"], x))
    return x + h, aux, rows


def _ssm_block(cfg, p, x):
    return x + ssm_mod.ssm_block(cfg, p["ssm"], norm_apply(cfg, p["ln1"], x))


def _maybe_remat(cfg, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def backbone(cfg: ModelConfig, params: dict, x: jax.Array,
             *, window: int | None = None
             ) -> tuple[jax.Array, jax.Array, dict]:
    """(B, S, D) -> (hidden (B, S, D), aux_loss (), stats). Full-sequence
    pass; ``stats`` is empty but for held-expert layers."""
    window = cfg.sliding_window if window is None else window
    kind = _block_kind(cfg)
    stats = {}

    if kind in ("dense", "moe"):
        def body(carry, lp):
            h, aux = carry
            h, a, rows = _attn_block(cfg, lp, h, window=window)
            return (h, aux + a), rows
        aux = jnp.zeros((), jnp.float32)
        for name in _stacks(params):
            (x, aux), rows = jax.lax.scan(_maybe_remat(cfg, body), (x, aux),
                                          params[name])
            if rows is not None:
                stats["expert_rows"] = rows
    elif kind == "ssm":
        def body(carry, lp):
            return _ssm_block(cfg, lp, carry), None
        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])
        aux = jnp.zeros((), jnp.float32)
    else:  # hybrid: groups of ssm + one shared attention block
        shared = params["shared_attn"]

        def group(carry, gp):
            h = carry

            def inner(c, lp):
                return _ssm_block(cfg, lp, c), None
            h, _ = jax.lax.scan(inner, h, gp)
            h, _, _ = _attn_block(cfg, shared, h, window=window)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(cfg, group), x,
                            params["ssm_layers"])
        aux = jnp.zeros((), jnp.float32)
    return norm_apply(cfg, params["final_norm"], x), aux, stats


def embed_tokens(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    x = embed_apply(cfg, params["tok"], batch["tokens"])
    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype)          # (B, P, D)
        patches = patches @ params["patch_proj"]["w"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
    return x


def hidden(cfg: ModelConfig, params: dict, batch: dict,
           *, window: int | None = None
           ) -> tuple[jax.Array, jax.Array, dict]:
    """Final-norm hidden states over text positions (pre-logits), aux,
    stats."""
    x = embed_tokens(cfg, params, batch)
    h, aux, stats = backbone(cfg, params, x, window=window)
    if cfg.family == "vlm":                      # logits only on text slots
        h = h[:, cfg.num_patches:]
    return h, aux, stats


def forward(cfg: ModelConfig, params: dict, batch: dict,
            *, window: int | None = None
            ) -> tuple[jax.Array, jax.Array, dict]:
    """Training/eval forward. Returns (logits over text positions, aux,
    stats)."""
    h, aux, stats = hidden(cfg, params, batch, window=window)
    return logits_apply(cfg, params["tok"], h), aux, stats


def lm_loss(cfg: ModelConfig, logits: jax.Array, tokens: jax.Array,
            weights: jax.Array | None = None) -> jax.Array:
    """Next-token CE, fp32. logits: (B,S,V); tokens: (B,S)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, tg[..., None], axis=-1)[..., 0]
    if weights is not None:
        w = weights[:, 1:]
        return jnp.sum(nll * w) / jnp.clip(jnp.sum(w), 1e-9)
    return jnp.mean(nll)


# --------------------------------------------------------------- serving

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=None) -> dict:
    dtype = dtype or dtype_of(cfg)
    kind = _block_kind(cfg)
    cache: dict[str, Any] = {"index": jnp.zeros((), jnp.int32)}
    if kind in ("dense", "moe"):
        cache["pos"] = jnp.full((cache_len,), -1, jnp.int32)
        layer_cache = attn.mla_cache_init if cfg.kv_lora_rank else \
            attn.cache_init
        depth = {"dense_layers": cfg.first_dense_layers,
                 "layers": cfg.num_layers - cfg.first_dense_layers}
        for name in STACKS:
            if depth[name]:
                cache[name] = jax.vmap(
                    lambda _: layer_cache(cfg, batch, cache_len, dtype)
                )(jnp.arange(depth[name]))
    elif kind == "ssm":
        cache["layers"] = jax.vmap(
            lambda _: ssm_mod.ssm_cache_init(cfg, batch, dtype)
        )(jnp.arange(cfg.num_layers))
    else:
        groups, per = _hybrid_shape(cfg)
        cache["pos"] = jnp.full((cache_len,), -1, jnp.int32)
        cache["ssm"] = jax.vmap(jax.vmap(
            lambda _: ssm_mod.ssm_cache_init(cfg, batch, dtype)))(
                jnp.arange(groups * per).reshape(groups, per))
        cache["attn"] = jax.vmap(
            lambda _: attn.cache_init(cfg, batch, cache_len, dtype)
        )(jnp.arange(groups))
    return cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                tokens: jax.Array, *, window: int | None = None
                ) -> tuple[jax.Array, dict]:
    """One-token decode. tokens: (B, 1). Returns (logits (B,1,V), cache)."""
    window = cfg.sliding_window if window is None else window
    kind = _block_kind(cfg)
    index = cache["index"]
    x = embed_apply(cfg, params["tok"], tokens)
    new_cache = dict(cache)

    if kind in ("dense", "moe"):
        pos_tags = cache["pos"]
        decode_attn = attn.mla_decode if cfg.kv_lora_rank else \
            attn.decode_self_attention

        def body(carry, scanned):
            h = carry
            lp, lc = scanned
            hn = norm_apply(cfg, lp["ln1"], h)
            a, updated = decode_attn(cfg, lp["attn"], hn, lc, index,
                                     pos_tags, window=window)
            h = h + a
            m, _, _ = _ffn(cfg, lp, norm_apply(cfg, lp["ln2"], h))
            return h + m, updated

        for name in _stacks(params):
            x, upd = jax.lax.scan(body, x, (params[name], cache[name]))
            new_cache["pos"] = upd.pop("pos")[0]  # identical across layers
            new_cache[name] = upd
    elif kind == "ssm":
        def body(carry, scanned):
            h = carry
            lp, lc = scanned
            o, nc = ssm_mod.ssm_decode_step(
                cfg, lp["ssm"], norm_apply(cfg, lp["ln1"], h), lc)
            return h + o, nc
        x, new_layers = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        new_cache["layers"] = new_layers
    else:  # hybrid
        shared = params["shared_attn"]
        pos_tags = cache["pos"]

        def group(carry, scanned):
            h = carry
            gp, gssm, gattn = scanned

            def inner(c, s):
                lp, lc = s
                o, nc = ssm_mod.ssm_decode_step(
                    cfg, lp["ssm"], norm_apply(cfg, lp["ln1"], c), lc)
                return c + o, nc
            h, ncs = jax.lax.scan(inner, h, (gp, gssm))
            hn = norm_apply(cfg, shared["ln1"], h)
            a, upd = attn.decode_self_attention(
                cfg, shared["attn"], hn, gattn, index, pos_tags,
                window=window)
            h = h + a
            h = h + mlp_apply(cfg, shared["mlp"],
                              norm_apply(cfg, shared["ln2"], h))
            return h, (ncs, {"k": upd["k"], "v": upd["v"],
                             "pos": upd["pos"]})

        x, (new_ssm, upd) = jax.lax.scan(
            group, x, (params["ssm_layers"], cache["ssm"], cache["attn"]))
        new_cache["ssm"] = new_ssm
        new_cache["attn"] = {"k": upd["k"], "v": upd["v"]}
        new_cache["pos"] = upd["pos"][0]
    new_cache["index"] = index + 1
    h = norm_apply(cfg, params["final_norm"], x)
    return logits_apply(cfg, params["tok"], h), new_cache


def _place(kv_s: jax.Array, cache_len: int) -> jax.Array:
    """Embed prefill KV (L, B, S, ...) at the head of a cache_len buffer."""
    pad = cache_len - kv_s.shape[2]
    if not pad:
        return kv_s
    return jnp.pad(kv_s, [(0, 0), (0, 0), (0, pad)] +
                   [(0, 0)] * (kv_s.ndim - 3))


def _pos_tags(s: int, cache_len: int) -> jax.Array:
    tags = jnp.full((cache_len,), -1, jnp.int32)
    return tags.at[:s].set(jnp.arange(s, dtype=jnp.int32))


def prefill(cfg: ModelConfig, params: dict, batch: dict,
            *, window: int | None = None,
            cache_len: int | None = None) -> tuple[jax.Array, dict]:
    """Full-sequence prefill: logits + a cache ready for decode at index S.

    ``cache_len`` >= S reserves decode headroom (defaults to S, which makes
    the cache a ring that immediately starts evicting — pass the full
    expected context for exact decoding).
    """
    window = cfg.sliding_window if window is None else window
    kind = _block_kind(cfg)
    x = embed_tokens(cfg, params, batch)
    b, s, _ = x.shape
    cache_len = max(cache_len or s, s)
    cache = init_cache(cfg, b, cache_len)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    if kind in ("dense", "moe"):
        def body(carry, lp):
            h = carry
            a, kv = _self_attention(cfg, lp["attn"],
                                    norm_apply(cfg, lp["ln1"], h),
                                    causal=True, window=window,
                                    positions=positions)
            h = h + a
            m, _, _ = _ffn(cfg, lp, norm_apply(cfg, lp["ln2"], h))
            return h + m, kv
        for name in _stacks(params):
            x, kvs = jax.lax.scan(body, x, params[name])
            cache[name] = jax.tree.map(lambda t: _place(t, cache_len), kvs)
        cache["pos"] = _pos_tags(s, cache_len)
    elif kind == "ssm":
        def body(carry, lp):
            h = carry
            hn = norm_apply(cfg, lp["ln1"], h)
            o, st = _ssm_block_with_state(cfg, lp["ssm"], hn)
            return h + o, st
        x, states = jax.lax.scan(body, x, params["layers"])
        cache["layers"] = states
    else:
        shared = params["shared_attn"]

        def group(carry, gp):
            h = carry

            def inner(c, lp):
                hn = norm_apply(cfg, lp["ln1"], c)
                o, st = _ssm_block_with_state(cfg, lp["ssm"], hn)
                return c + o, st
            h, sts = jax.lax.scan(inner, h, gp)
            a, kv = attn.self_attention(cfg, shared["attn"],
                                        norm_apply(cfg, shared["ln1"], h),
                                        causal=True, window=window,
                                        positions=positions)
            h = h + a
            h = h + mlp_apply(cfg, shared["mlp"],
                              norm_apply(cfg, shared["ln2"], h))
            return h, (sts, kv)
        x, (ssm_sts, kvs) = jax.lax.scan(group, x, params["ssm_layers"])
        cache["ssm"] = ssm_sts
        cache["attn"] = jax.tree.map(lambda t: _place(t, cache_len), kvs)
        cache["pos"] = _pos_tags(s, cache_len)

    cache["index"] = jnp.asarray(s, jnp.int32)
    if cfg.family == "vlm":
        x = x[:, cfg.num_patches:]
    h = norm_apply(cfg, params["final_norm"], x)
    return logits_apply(cfg, params["tok"], h), cache


def _ssm_block_with_state(cfg, p, u):
    """Like ssm_mod.ssm_block but also returns the decode cache."""
    from .ssm import _causal_conv, _dims, _split_conv, _split_proj
    from .layers import dense_apply, rms_norm
    d_in, heads, n, g, conv_ch, _ = _dims(cfg)
    bsz, l, _ = u.shape
    zxbcdt = dense_apply(p["in_proj"], u)
    z, xc_raw, dt = _split_proj(cfg, zxbcdt)
    xc = _causal_conv(p["conv_w"], p["conv_b"], xc_raw)
    x, b_mat, c_mat = _split_conv(cfg, xc)
    x = x.reshape(bsz, l, heads, cfg.ssm_headdim)
    b_mat = b_mat.reshape(bsz, l, g, n)
    c_mat = c_mat.reshape(bsz, l, g, n)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    from ..kernels import ops
    y, hT = ops.ssd(x, dtf, a, b_mat, c_mat, chunk=cfg.ssm_chunk)
    y = y + x * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(bsz, l, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = dense_apply(p["out_proj"], y)
    conv_tail = xc_raw[:, -(cfg.ssm_conv - 1):, :]
    return out, {"conv": conv_tail, "state": hT}
