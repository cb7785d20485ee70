"""Aggregator implementations: merging admitted updates (Alg. 2 line 21).

``WeightedAverageAggregator`` — size-weighted FedAvg over the admitted
                                mask (``core.aggregation.aggregate``).
``FusedAverageAggregator``    — the same mean as ONE flat segment-reduce
                                (``core.aggregation.fused_aggregate``):
                                every leaf flattened into a single (M, P)
                                buffer, reduced in one kernel (Pallas or
                                xla) — float32-tolerance equal to
                                ``weighted``, not bitwise, so it is an
                                opt-in (``aggregator="fused"``) rather
                                than the golden-history default.
``ScaffoldAggregator``        — the same average, then the SCAFFOLD damped
                                server step w_g <- w_g + eta_g*(avg - w_g).
``DeviceConcatAggregator``    — FedCAT (arXiv 2202.12751): identity within
                                a chain, size-weighted average across the
                                chains' representative models.
``PerClusterAggregator``      — clustered FL: masks any base aggregator
                                over the K-center cluster axis (one
                                admitted-member average per center; empty
                                clusters keep their center unchanged).

Each aggregator call is ONE compiled program: its arithmetic is a jitted
function of arrays only (the global params, the stacked client params,
sizes, mask, and the chain or cluster ids it reads from the cohort's
outputs), named so a profile shows it (``jit_aggregate``,
``jit_fused_aggregate``, ``jit_scaffold_aggregate``,
``jit_devconcat_aggregate``, ``jit_perclstr_aggregate``). The jit lives
here rather than at the engines' call sites, so host-side wrappers around
an aggregator keep working.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.aggregation import aggregate, fused_aggregate
from .registry import register

@partial(jax.jit, static_argnames=("lr_g",))
def scaffold_aggregate(global_params, stacked_params, sizes, mask, lr_g):
    avg = aggregate(stacked_params, sizes, mask)
    return jax.tree.map(
        lambda wg, ag: wg + lr_g * (ag.astype(wg.dtype) - wg),
        global_params, avg)


@jax.jit
def devconcat_aggregate(global_params, stacked_params, sizes, mask,
                        gid, pos):
    m = jnp.asarray(mask, jnp.float32)
    same = gid[None, :] == gid[:, None]
    prefix = same & (pos[None, :] <= pos[:, None])
    # ok[i]: every chain stage up to and including i was admitted
    ok = jnp.all(jnp.where(prefix, m > 0, True), axis=1)
    # the deepest unbroken stage represents its chain
    deeper = same & (pos[None, :] > pos[:, None])
    rep = (ok & ~jnp.any(deeper & ok[None, :], axis=1)).astype(jnp.float32)
    # chain weight: total data size along the admitted prefix
    w = jnp.sum(jnp.where(prefix, jnp.asarray(sizes, jnp.float32)[None, :],
                          0.0), axis=1)
    avg = aggregate(stacked_params, w, rep)
    kept = jnp.sum(w * rep) > 0
    return jax.tree.map(
        lambda ag, wg: jnp.where(kept, ag, wg.astype(ag.dtype)),
        avg, global_params)


@partial(jax.jit, static_argnames=("base",))
def perclstr_aggregate(global_params, out, sizes, mask, base):
    cids = jnp.asarray(out["cluster"], jnp.int32)
    sizes = jnp.asarray(sizes, jnp.float32)
    mask = jnp.asarray(mask, jnp.float32)
    k = jax.tree.leaves(global_params)[0].shape[0]
    centers = []
    for c in range(k):
        member = (cids == c).astype(jnp.float32)
        mk = mask * member
        old = jax.tree.map(lambda s: s[c], global_params)
        avg = base(old, out, sizes, mk)
        kept = jnp.sum(sizes * mk) > 0
        centers.append(jax.tree.map(
            lambda a, o: jnp.where(kept, a.astype(o.dtype), o), avg, old))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *centers)


@register("aggregator", "weighted")
class WeightedAverageAggregator:
    """w_g = sum_{i in A} L_i W_i / sum_{i in A} L_i."""

    @classmethod
    def from_config(cls, config, local):
        return cls()

    def __call__(self, global_params, out, sizes, mask):
        return aggregate(out["params"], sizes, mask)


@register("aggregator", "fused")
class FusedAverageAggregator:
    """``weighted``'s mean as one flat (M, P) segment-reduce.

    ``backend="pallas"`` tiles the flattened param axis through the VMEM
    kernel (``repro.kernels.fused_aggregate``); ``None``/"xla" uses the
    fused-jnp reference. Like ``weighted`` it is one compiled program;
    it differs only by reducing the flat ``(M, P)`` concatenate of every
    leaf in one kernel, which costs an extra HBM pass over the cohort.
    """

    def __init__(self, backend: str | None = None):
        self.backend = backend

    @classmethod
    def from_config(cls, config, local):
        return cls()

    def __call__(self, global_params, out, sizes, mask):
        return fused_aggregate(out["params"], sizes, mask,
                               backend=self.backend)


@register("aggregator", "scaffold")
class ScaffoldAggregator:
    """Weighted average followed by a global step of size ``lr_g``."""

    def __init__(self, lr_g: float = 1.0):
        self.lr_g = float(lr_g)

    @classmethod
    def from_config(cls, config, local):
        return cls(local.scaffold_lr_g)

    def __call__(self, global_params, out, sizes, mask):
        return scaffold_aggregate(global_params, out["params"], sizes, mask,
                                  lr_g=self.lr_g)


@register("aggregator", "devconcat")
class DeviceConcatAggregator:
    """FedCAT merge: one model per chain, size-weighted across chains.

    ``out`` rows are per-device chain-stage outputs (device i's params are
    the chain state after i trained), annotated with ``group_id``/
    ``chain_pos`` by ``CatChainStrategy``. Within a chain the merge is the
    identity: the deepest stage whose admitted prefix is unbroken IS the
    group's model — it already contains its predecessors' training. Across
    chains those representatives average weighted by their admitted-prefix
    data sizes. Judgment therefore filters chain membership *before*
    concatenation: a rejected device truncates its chain at the last stage
    it never touched. A chain whose first device is rejected contributes
    nothing; if every chain is emptied the global model is kept unchanged.

    With group size 1 every device is its own chain and this reduces
    exactly (bit-for-bit) to ``WeightedAverageAggregator``. Cohorts
    without chain annotations degrade to the same plain weighted average.
    """

    @classmethod
    def from_config(cls, config, local):
        return cls()

    def __call__(self, global_params, out, sizes, mask):
        if "group_id" not in out:        # not a chain cohort: plain FedAvg
            return aggregate(out["params"], sizes, mask)
        return devconcat_aggregate(global_params, out["params"], sizes,
                                   mask, out["group_id"], out["chain_pos"])


@register("aggregator", "perclstr")
class PerClusterAggregator:
    """Clustered merge: the base aggregator's weighted mean, masked over
    the cluster axis.

    On a clustered round ``global_params`` is the :class:`ModelBank`'s
    stacked (K, ...) pytree and ``out["cluster"]`` carries the round's
    per-client cluster ids; each center averages ONLY its own admitted
    members (``mask * (cluster == k)``) through the base aggregator, and
    a cluster with no admitted member this round keeps its center
    unchanged (the ``DeviceConcatAggregator`` empty-chain guard —
    ``masked_mean_tree``'s eps-clipped denominator would otherwise zero
    the center out).

    Unclustered cohorts (no ``"cluster"`` key — every K=1 round) pass
    straight through to the base aggregator, so ``ifca+maxent`` at K=1
    is bit-for-bit the ``weighted`` seed path.
    """

    def __init__(self, base=None):
        self.base = base if base is not None \
            else WeightedAverageAggregator()

    @classmethod
    def from_config(cls, config, local):
        return cls()

    def __call__(self, global_params, out, sizes, mask):
        if "cluster" not in out:
            return self.base(global_params, out, sizes, mask)
        # only what an aggregator reads crosses the jit boundary, so the
        # compile cache never keys on the cohort's other outputs
        read = {k: out[k] for k in ("params", "group_id", "chain_pos",
                                    "cluster") if k in out}
        return perclstr_aggregate(global_params, read, sizes, mask,
                                  base=self.base)
