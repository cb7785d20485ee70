"""Mesh-scale FedEntropy: the paper's round as ONE pjit-able train step.

Cross-silo mapping (DESIGN.md §2.2): the global batch is tiled into M client
groups along the ("pod","data") mesh axes. With one local step (E=1), masked
FedAvg of per-client gradients is EXACTLY the gradient of the
mask-and-size-weighted loss — so the whole round fuses into a single
forward+backward:

  1. forward -> logits; per-client soft labels = mean softmax over the
     client's tokens (paper Eq. 2), under stop_gradient;
  2. maximum-entropy judgment (Alg. 1 as lax.while_loop) -> mask (M,);
  3. loss = sum_m mask_m * size_m * loss_m / sum_m mask_m * size_m
     (paper Alg. 2 line 21 at gradient level); backward reuses the
     forward's activations — zero extra passes.

Semantics note (recorded in DESIGN.md): the paper judges soft labels of the
*locally updated* models; at E=1 the update direction is the same gradient
being aggregated, so judging pre-update logits is the first-order-consistent
formulation. The vmapped simulator (core/simulator.py) keeps the exact
multi-epoch semantics for models that fit per-client. Soft labels stay
full-vocabulary (paper Eq. 2): V floats per client is negligible next to
model bytes, which is the paper's entire communication argument.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models.api import Model
from ..optim import Optimizer
from ..sharding.ctx import shard_act
from .judgment import judge


@dataclass(frozen=True)
class FedSpec:
    num_clients: int = 16          # M client groups tiled over batch axes
    enabled: bool = True           # False -> plain data-parallel baseline
    eps_tol: float = 1e-6
    # §Perf: stream the vocab projection + CE + soft-label accumulation in
    # sequence chunks instead of materializing (B, S, V) logits.
    chunked_head: bool = False
    seq_chunk: int = 512


def chunked_head_stats(cfg: ModelConfig, tok_params: dict, h: jax.Array,
                       tokens: jax.Array, m: int, seq_chunk: int = 512
                       ) -> tuple[jax.Array, jax.Array]:
    """Per-client (loss (M,), soft labels (M, V)) without a full logits
    tensor: lax.scan over sequence chunks computes the vocab projection,
    next-token CE and softmax accumulation per chunk and discards the
    chunk logits. Peak head activations drop from O(B*S*V) to
    O(B*seq_chunk*V). Each chunk is rematerialized for the backward.
    """
    from ..models.layers import logits_apply
    b, s, d = h.shape
    v = cfg.padded_vocab
    sc = min(seq_chunk, s)
    pad = (sc - s % sc) % sc
    nb = (s + pad) // sc
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    hp = jnp.moveaxis(hp.reshape(b, nb, sc, d), 1, 0)      # (nb,B,sc,D)
    # target for position j is tokens[j+1]; weight 0 at j >= S-1
    tgt = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad + 1)))
    tgt = jnp.moveaxis(tgt.reshape(b, nb, sc), 1, 0)
    base = jnp.arange(nb) * sc

    def chunk(carry, inp):
        nll_sum, soft_sum = carry
        hc, tc, b0 = inp
        logits = logits_apply(cfg, tok_params, hc).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        pos = b0 + jnp.arange(sc)[None, :]                 # (1, sc)
        wgt = (pos < s - 1).astype(jnp.float32)            # next-token mask
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        nll_sum = nll_sum + jnp.sum(
            (nll * wgt).reshape(m, -1), axis=1)
        probs = jax.lax.stop_gradient(jnp.exp(logp))
        svalid = (pos < s).astype(jnp.float32)             # Eq.2: all pos
        soft_sum = soft_sum + jnp.einsum(
            "mtv->mv", (probs * svalid[..., None]).reshape(m, -1, v))
        return (nll_sum, soft_sum), None

    init = (jnp.zeros((m,), jnp.float32), jnp.zeros((m, v), jnp.float32))
    (nll_sum, soft_sum), _ = jax.lax.scan(
        jax.checkpoint(chunk), init, (hp, tgt, base))
    per_client = nll_sum / ((s - 1) * (b // m))
    soft = soft_sum / (s * (b // m))
    return per_client, shard_act(soft, ("fl_clients", "vocab"))


def per_client_soft_labels(logits: jax.Array, m: int) -> jax.Array:
    """(B, S, V) -> (M, V) mean softmax per client group (paper Eq. 2)."""
    b, s, v = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = probs.reshape(m, (b // m) * s, v)
    soft = jnp.mean(probs, axis=1)
    return shard_act(soft, ("fl_clients", "vocab"))


def _per_client_loss(cfg: ModelConfig, logits, tokens, m):
    """(M,) mean next-token CE per client group."""
    lg = logits[:, :-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, tg[..., None], axis=-1)[..., 0]
    b = nll.shape[0]
    return jnp.mean(nll.reshape(m, -1), axis=1)


def make_train_step(
    model: Model,
    opt: Optimizer,
    fed: FedSpec,
    judge_fn: Callable | None = None,
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). ``batch`` needs "tokens" (+family extras) and optionally
    "client_sizes" (M,) — defaults to uniform.

    ``judge_fn`` is the traced judge axis: (soft_labels, sizes) ->
    ``JudgmentResult``. Defaults to the maximum-entropy judgment; pass a
    ``repro.fl`` judge's ``.traced()`` to run any registered judge (or the
    Pallas-backed sweep) inside the jitted step.

    The model's stats join the metrics: a model with held-expert layers
    (``cfg.experts_held``) adds ``expert_rows`` (MoE layers, experts
    held), the assignments each held expert computed."""
    cfg = model.cfg
    if judge_fn is None:
        judge_fn = judge

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        m = fed.num_clients
        if fed.chunked_head:
            h, aux, stats = model.hidden(params, batch)
            client_loss, soft = chunked_head_stats(
                cfg, params["tok"], h, tokens, m, fed.seq_chunk)
        else:
            logits, aux, stats = model.forward(params, batch)
            client_loss = _per_client_loss(cfg, logits, tokens, m)  # (M,)
            soft = None
        sizes = batch.get(
            "client_sizes", jnp.ones((m,), jnp.float32))

        if fed.enabled:
            if soft is None:
                soft = per_client_soft_labels(
                    jax.lax.stop_gradient(logits), m)
            jr = judge_fn(soft, jax.lax.stop_gradient(sizes))
            mask = jax.lax.stop_gradient(jr.mask)
            ent, ent0 = jr.entropy, jr.initial_entropy
        else:
            mask = jnp.ones((m,), jnp.float32)
            ent = ent0 = jnp.zeros(())

        w = mask * sizes
        loss = jnp.sum(w * client_loss) / jnp.clip(jnp.sum(w), 1e-9)
        loss = loss + cfg.router_aux_weight * aux
        metrics = {
            "loss": loss,
            "aux_loss": aux,
            "mask": mask,
            "num_positive": jnp.sum(mask),
            "entropy": ent,
            "entropy_initial": ent0,
            "per_client_loss": client_loss,
        }
        metrics.update(stats)
        return loss, metrics

    def train_step(params, opt_state, batch):
        grads, metrics = jax.grad(loss_fn, has_aux=True)(params, batch)
        new_params, new_state = opt.update(grads, opt_state, params)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        metrics = dict(metrics, grad_norm=gnorm)
        return new_params, new_state, metrics

    return train_step


def make_microbatched_train_step(
    model: Model,
    opt: Optimizer,
    fed: FedSpec,
    num_microbatches: int,
    judge_fn: Callable | None = None,
) -> Callable:
    """Two-phase microbatched FedEntropy round — the paper's two-stage
    protocol made literal, and the memory lever for models whose
    activations don't fit at full global batch (kimi-k2 train_4k):

    Phase 1 (paper stage 1): forward-only scan over microbatches
    accumulating per-client soft-label sums and losses; judge ONCE on the
    full-batch soft labels (identical mask to the unbatched step).
    Phase 2 (paper stage 2): gradient-accumulation scan over the same
    microbatches with the judged mask weighting each client's loss.

    Peak activation memory drops ~num_microbatches-fold; compute cost is
    one extra forward (phase 1), the classic remat-style trade.

    ``judge_fn`` as in :func:`make_train_step` — the same traced judge
    axis plugs into both step builders.
    """
    cfg = model.cfg
    if judge_fn is None:
        judge_fn = judge

    def _split(batch):
        def sp(x):
            b = x.shape[0]
            mb = b // num_microbatches
            # keep client interleaving: (B,) -> (n_mb, M, B/M/n_mb, ...)
            m = fed.num_clients
            per = b // m
            x2 = x.reshape(m, per, *x.shape[1:])
            x2 = x2.reshape(m, num_microbatches, per // num_microbatches,
                            *x.shape[1:])
            return jnp.moveaxis(x2, 1, 0).reshape(
                num_microbatches, m * (per // num_microbatches),
                *x.shape[1:])
        return jax.tree.map(sp, batch)

    def phase1(params, mbatches):
        m = fed.num_clients
        v = cfg.padded_vocab

        def body(carry, mb):
            soft_sum, loss_sum = carry
            logits, _, _ = model.forward(params, mb)
            soft = per_client_soft_labels(logits, m)
            loss = _per_client_loss(cfg, logits, mb["tokens"], m)
            return (soft_sum + soft, loss_sum + loss), None

        (soft_sum, loss_sum), _ = jax.lax.scan(
            body, (jnp.zeros((m, v), jnp.float32),
                   jnp.zeros((m,), jnp.float32)), mbatches)
        return soft_sum / num_microbatches, loss_sum / num_microbatches

    def train_step(params, opt_state, batch):
        m = fed.num_clients
        mbatches = _split(batch)
        sizes = jnp.ones((m,), jnp.float32)

        if fed.enabled:
            soft, _ = phase1(params, mbatches)
            jr = judge_fn(jax.lax.stop_gradient(soft), sizes)
            mask = jax.lax.stop_gradient(jr.mask)
            ent, ent0 = jr.entropy, jr.initial_entropy
        else:
            mask = jnp.ones((m,), jnp.float32)
            ent = ent0 = jnp.zeros(())

        w = mask * sizes

        def mb_loss(p, mb):
            logits, aux, _ = model.forward(p, mb)
            client_loss = _per_client_loss(cfg, logits, mb["tokens"], m)
            loss = jnp.sum(w * client_loss) / jnp.clip(jnp.sum(w), 1e-9)
            return loss + cfg.router_aux_weight * aux, client_loss

        grad_fn = jax.grad(mb_loss, has_aux=True)

        def acc_body(carry, mb):
            g_acc, l_acc, cl_acc = carry
            g, cl = grad_fn(params, mb)
            g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
            loss = jnp.sum(w * cl) / jnp.clip(jnp.sum(w), 1e-9)
            return (g_acc, l_acc + loss, cl_acc + cl), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum, cl_sum), _ = jax.lax.scan(
            acc_body, (zeros, jnp.zeros(()), jnp.zeros((m,))), mbatches)
        grads = jax.tree.map(lambda g: g / num_microbatches, grads)

        new_params, new_state = opt.update(grads, opt_state, params)
        metrics = {
            "loss": loss_sum / num_microbatches,
            "mask": mask,
            "num_positive": jnp.sum(mask),
            "entropy": ent,
            "entropy_initial": ent0,
            "per_client_loss": cl_sum / num_microbatches,
        }
        return new_params, new_state, metrics

    return train_step


def make_serve_steps(model: Model, *, window: int | None = None):
    """(prefill_step, decode_step) for the serving shapes."""
    def prefill_step(params, batch):
        return model.prefill(params, batch, window=window)

    def decode_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens, window=window)

    return prefill_step, decode_step


# ---------------------------------------------------------------- specs

# logical axes for the trailing dims of each param, keyed by path suffix.
_PARAM_RULES: list[tuple[tuple[str, ...], tuple]] = [
    (("tok", "embed"), ("vocab", "embed")),
    (("tok", "head"), ("embed", "vocab")),
    (("patch_proj", "w"), ("embed", None)),
    (("attn", "w_q", "w"), ("embed", "heads")),
    (("attn", "w_k", "w"), ("embed", "kv_heads")),
    (("attn", "w_v", "w"), ("embed", "kv_heads")),
    (("attn", "w_o", "w"), ("heads", "embed")),
    (("attn", "w_kva", "w"), ("embed", None)),
    (("attn", "w_kvb", "w"), (None, "heads")),
    (("xattn", "w_q", "w"), ("embed", "heads")),
    (("xattn", "w_k", "w"), ("embed", "kv_heads")),
    (("xattn", "w_v", "w"), ("embed", "kv_heads")),
    (("xattn", "w_o", "w"), ("heads", "embed")),
    (("mlp", "w_in", "w"), ("embed", "ffn")),
    (("mlp", "w_gate", "w"), ("embed", "ffn")),
    (("mlp", "w_out", "w"), ("ffn", "embed")),
    (("shared", "w_in", "w"), ("embed", "ffn")),
    (("shared", "w_gate", "w"), ("embed", "ffn")),
    (("shared", "w_out", "w"), ("ffn", "embed")),
    (("moe", "router", "w"), ("embed", "experts")),
    (("moe", "w_in"), ("experts", "embed", "ffn")),
    (("moe", "w_gate"), ("experts", "embed", "ffn")),
    (("moe", "w_out"), ("experts", "ffn", "embed")),
    (("ssm", "in_proj", "w"), ("embed", "ssm_inner")),
    (("ssm", "out_proj", "w"), ("ssm_inner", "embed")),
    (("ssm", "conv_w"), (None, "ssm_inner")),
    (("ssm", "conv_b"), ("ssm_inner",)),
    (("ssm", "norm_scale"), ("ssm_inner",)),
]


def _path_names(path) -> tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_logical_axes(params_shape) -> Any:
    """Tree of logical-axis tuples matching ``jax.eval_shape(init)`` output.

    Rules are matched on path suffixes; the rule's axes bind to the TRAILING
    dims, leading (layer-stacking) dims get None. Unmatched leaves (norms,
    biases, scalars) replicate.
    """
    def one(path, leaf):
        names = _path_names(path)
        for suffix, axes in _PARAM_RULES:
            if names[-len(suffix):] == suffix:
                pad = leaf.ndim - len(axes)
                if pad < 0:       # rank-reduced (e.g. unstacked) — replicate
                    return (None,) * leaf.ndim
                return (None,) * pad + tuple(axes)
        return (None,) * leaf.ndim

    return jax.tree_util.tree_map_with_path(one, params_shape)


# cache logical axes: shard batch dim + kv heads/ssm state over model axis.
# The cache TIME dim carries the "kv_time" logical name: by default it maps
# to no mesh axis, but architectures whose kv_heads don't divide the model
# axis (chatglm kv=2, kimi kv=8 on a 16-way axis) can route it to "model"
# via a rules override — otherwise their caches replicate model_size-fold.
def cache_logical_axes(cache_shape) -> Any:
    def one(path, leaf):
        names = _path_names(path)
        last = names[-1] if names else ""
        if last in ("k", "v"):        # (L, B, T, K, hd) or (B, T, K, hd)
            pad = leaf.ndim - 4
            return (None,) * pad + ("batch", "kv_time", "kv_heads", None)
        if last in ("c_kv", "k_pe"):  # MLA latent (L, B, T, r)
            pad = leaf.ndim - 3
            return (None,) * pad + ("batch", "kv_time", None)
        if last == "state":           # (.., B, H, P, N)
            pad = leaf.ndim - 4
            return (None,) * pad + ("batch", "ssm_inner", None, None)
        if last == "conv":            # (.., B, K-1, C)
            pad = leaf.ndim - 3
            return (None,) * pad + ("batch", None, "ssm_inner")
        return (None,) * leaf.ndim

    return jax.tree_util.tree_map_with_path(one, cache_shape)
