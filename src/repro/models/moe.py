"""Top-k MoE with sort-based static-shape dispatch, expert-parallel aware.

Dispatch (TPU-native, no dynamic shapes):
  1. router top-k over experts -> (T, k) indices + renormalized probs;
  2. flatten assignments, stable-argsort by expert id;
  3. position-in-expert = rank - first-rank-of-expert (via searchsorted);
  4. scatter tokens into an (E, C, D) capacity buffer (overflow dropped —
     standard capacity-factor semantics), expert einsum, gather back,
     combine with gate probs.

Sharding: experts -> "model" axis; the capacity axis -> batch axes. Under
pjit the dispatch scatter/gather lowers to all-to-all-like collectives;
the §Perf pass replaces this with an explicit shard_map lax.all_to_all.

Aux load-balance loss (Switch-style): E * sum_e f_e * p_e, where f_e is the
fraction of tokens routed to e and p_e the mean router prob.

``moe_held`` is the other expert layer, DeepSeek-V3's, selected by
``cfg.experts_held`` > 0: it holds a contiguous share of the experts, routes
over all of them, and computes only its share's part of the result for the
tokens routed there, with no capacity (dropless), plus the shared experts.
On one chip it runs without the expert-parallel exchange.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding.ctx import shard_act
from .layers import dense_init, mlp_apply, mlp_init, pdtype_of


def moe_init(key, cfg: ModelConfig) -> dict:
    """Router over every expert and the experts this layer holds (all of
    them but for a held-expert layer, which adds the router's selection
    bias and the shared experts)."""
    e = cfg.experts_held or cfg.num_experts
    d, f = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    std_in, std_out = d ** -0.5, f ** -0.5
    p = {
        "router": dense_init(ks[0], cfg, d, cfg.num_experts, scale=0.02),
        "w_in": (jax.random.normal(ks[1], (e, d, f)) * std_in).astype(
            pdtype_of(cfg)),
        "w_gate": (jax.random.normal(ks[2], (e, d, f)) * std_in).astype(
            pdtype_of(cfg)),
        "w_out": (jax.random.normal(ks[3], (e, f, d)) * std_out).astype(
            pdtype_of(cfg)),
    }
    if cfg.experts_held:
        p["router"]["bias"] = jnp.zeros((cfg.num_experts,), pdtype_of(cfg))
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(jax.random.fold_in(key, 1), cfg, d,
                               cfg.num_shared_experts * f)
    return p


def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    k, e = cfg.experts_per_token, cfg.num_experts
    c = int(num_tokens * k / e * cfg.moe_capacity_factor)
    # MXU-friendly multiple of 8, at least 4
    return max(4, (c + 7) // 8 * 8)


def _route(cfg: ModelConfig, router_w, xt: jax.Array):
    """Shared routing math. xt: (T, D) -> (top_p, top_i, aux)."""
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = (xt @ router_w.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    top_p, top_i = jax.lax.top_k(probs, k)                   # (T, k)
    top_p = top_p / jnp.clip(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return top_p, top_i, aux


def _dispatch_indices(cfg: ModelConfig, top_i: jax.Array):
    """Sort-based dispatch bookkeeping. top_i: (T, k)."""
    t, k = top_i.shape
    flat_e = top_i.reshape(-1)                               # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(t * k) - first
    token_of = order // k
    return order, sorted_e, pos, token_of


def moe_block_shard_map(cfg: ModelConfig, p: dict, x: jax.Array,
                        mesh) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map + lax.all_to_all (GShard-style).

    Experts live on the "model" axis; expert weights are additionally
    FSDP-sharded on the batch axes and all-gathered per layer. Dispatch:
    local sort-based pack into an (E, C_loc, D) buffer -> all_to_all over
    "model" (split experts / concat capacity) -> local expert einsum ->
    all_to_all back -> local combine. All collectives are explicit, so the
    roofline collective term reads straight off the HLO.

    This is the production path; the pjit path below is the naive variant
    kept for comparison (XLA replicates its scatter — see EXPERIMENTS §Perf).
    """
    from jax.sharding import PartitionSpec as P

    bsz, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_batch = int(np.prod([mesh.shape[a] for a in batch_axes]))
    ep = mesh.shape["model"]
    e_loc = e // ep
    # tokens must also split over the "model" axis or every model rank
    # routes identical copies and each expert does ep-x redundant work.
    b_loc = bsz // n_batch
    if s % ep == 0:
        xspec_dims = (batch_axes if batch_axes else None, "model", None)
        t_loc = b_loc * (s // ep)
        tok_axes = batch_axes + ("model",)
    elif b_loc % ep == 0:
        xspec_dims = (batch_axes + ("model",), None, None)
        t_loc = (b_loc // ep) * s
        tok_axes = batch_axes + ("model",)
    else:  # replicate over model (tiny decode batches only)
        xspec_dims = (batch_axes if batch_axes else None, None, None)
        t_loc = b_loc * s
        tok_axes = batch_axes
    cap = _capacity(cfg, t_loc)

    def local(xb, router_w, w_in, w_gate, w_out):
        # xb: (B_loc, S, D); w_*: (E_loc, D_loc, F) FSDP-sharded on D
        if batch_axes:
            w_in_f = jax.lax.all_gather(w_in, batch_axes, axis=1,
                                        tiled=True)
            w_gate_f = jax.lax.all_gather(w_gate, batch_axes, axis=1,
                                          tiled=True)
            w_out_f = jax.lax.all_gather(w_out, batch_axes, axis=2,
                                         tiled=True)
        else:
            w_in_f, w_gate_f, w_out_f = w_in, w_gate, w_out
        xt = xb.reshape(-1, d)                               # (T_loc, D)
        top_p, top_i, aux = _route(cfg, router_w, xt)
        if tok_axes:
            aux = jax.lax.pmean(aux, tok_axes)
        order, sorted_e, pos, token_of = _dispatch_indices(cfg, top_i)

        buf = jnp.zeros((e, cap, d), x.dtype)
        buf = buf.at[sorted_e, pos].set(xt[token_of], mode="drop")
        # exchange: split experts over "model", gather capacity shards
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)                 # (E_loc, C*ep, D)
        h = jnp.einsum("ecd,edf->ecf", buf, w_in_f.astype(x.dtype))
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate_f.astype(x.dtype))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                       w_out_f.astype(x.dtype))
        y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                               tiled=True)                   # (E, C, D)
        gathered = y[sorted_e, pos]
        kept = (pos < cap)[:, None].astype(x.dtype)
        gate = top_p.reshape(-1)[order][:, None].astype(x.dtype)
        out = jnp.zeros((t_loc, d), x.dtype).at[token_of].add(
            gathered * gate * kept)
        return out.reshape(xb.shape), aux

    bspec = P(*xspec_dims)
    wspec_in = P("model", batch_axes if batch_axes else None, None)
    wspec_out = P("model", None, batch_axes if batch_axes else None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec, P(None, None), wspec_in, wspec_in, wspec_out),
        out_specs=(bspec, P()),
        check_vma=False)
    out, aux = fn(x, p["router"]["w"], p["w_in"], p["w_gate"], p["w_out"])
    return out, aux


def moe_block(cfg: ModelConfig, p: dict, x: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss ()).

    Uses the shard_map expert-parallel path when a mesh context with a
    "model" axis is active and the batch divides the batch axes; otherwise
    the single-device pjit path.
    """
    from ..sharding import ctx as shard_ctx
    c = shard_ctx.current()
    if c is not None and "model" in c.mesh.shape and \
            cfg.num_experts % c.mesh.shape["model"] == 0:
        batch_axes = tuple(a for a in ("pod", "data") if a in c.mesh.shape)
        n_batch = int(np.prod([c.mesh.shape[a] for a in batch_axes]))
        if x.shape[0] % max(n_batch, 1) == 0:
            return moe_block_shard_map(cfg, p, x, c.mesh)
    return moe_block_pjit(cfg, p, x)


def moe_block_pjit(cfg: ModelConfig, p: dict, x: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Naive data-parallel-friendly MoE (reference path)."""
    bsz, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = bsz * s
    cap = _capacity(cfg, t)
    xt = x.reshape(t, d)

    top_p, top_i, aux = _route(cfg, p["router"]["w"], xt)
    order, sorted_e, pos, token_of = _dispatch_indices(cfg, top_i)

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[sorted_e, pos].set(xt[token_of], mode="drop")
    buf = shard_act(buf, ("experts", "capacity", None))

    # ---- expert computation ------------------------------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"].astype(x.dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(x.dtype))
    y = shard_act(y, ("experts", "capacity", None))

    # ---- combine -----------------------------------------------------------
    gathered = y[sorted_e, pos]                               # (T*k, D)
    kept = (pos < cap)[:, None].astype(x.dtype)
    gate = top_p.reshape(-1)[order][:, None].astype(x.dtype)
    contrib = gathered * gate * kept
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)
    out = out.reshape(bsz, s, d)
    return shard_act(out, ("batch", "seq", "embed")), aux


# ------------------------------------------------------------ held experts


def route_topk(cfg: ModelConfig, router: dict, xt: jax.Array):
    """(T, D) -> (weights (T, k) f32, experts (T, k)). Sigmoid scores are
    computed in float32 at full precision; the bias moves the choice of
    the top-k only; the chosen scores are renormalised and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(xt.astype(jnp.float32),
                                    router["w"].astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST))
    _, top_i = jax.lax.top_k(scores + router["bias"].astype(jnp.float32),
                             cfg.experts_per_token)
    w = jnp.take_along_axis(scores, top_i, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling, top_i


def moe_held(cfg: ModelConfig, p: dict, x: jax.Array, rank: int = 0
             ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), rows (experts_held,) int32: the
    assignments each held expert computed). The layer holds experts
    [rank * experts_held, (rank + 1) * experts_held).

    The (token, choice) assignments are sorted by held expert, those of
    other experts last; a token has at most min(k, held) held choices, so
    the first T * min(k, held) sorted rows hold every held assignment and
    none is dropped. The held experts' SwiGLU is three grouped products
    over those rows (``ops.gmm``); each row's output, times its weight, is
    added back to its token."""
    bsz, s, d = x.shape
    t, k, held = bsz * s, cfg.experts_per_token, cfg.experts_held
    xt = x.reshape(t, d)
    w, top_i = route_topk(cfg, p["router"], xt)
    local = top_i - rank * held
    local = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(local, stable=True)[: t * min(k, held)]
    rows = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    token_of = order // k
    xs = xt[token_of]
    h = jax.nn.silu(ops.gmm(xs, p["w_gate"].astype(x.dtype), rows)) * \
        ops.gmm(xs, p["w_in"].astype(x.dtype), rows)
    y = ops.gmm(h, p["w_out"].astype(x.dtype), rows)
    gate = jnp.where(jnp.arange(order.shape[0]) < jnp.sum(rows),
                     w.reshape(-1)[order], 0.0).astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(y * gate[:, None])
    out = out.reshape(bsz, s, d)
    if "shared" in p:
        out = out + mlp_apply(cfg, p["shared"], x)
    return shard_act(out, ("batch", "seq", "embed")), rows
