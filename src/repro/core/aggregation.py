"""Model aggregation (paper Alg. 2 line 21) over pytrees.

``aggregate``         — size-weighted FedAvg of stacked client params,
                        restricted to the positive mask (w_g = sum_i L_i w_i
                        / sum_i L_i over i in A).
``masked_mean_tree``  — generic masked weighted mean over a leading client
                        axis of every leaf.
``fused_aggregate``   — the same reduction as one flat segment-reduce:
                        every leaf reshaped into a single (M, P) buffer and
                        summed in one kernel (Pallas or xla).
``comm_bytes``        — accounting helper: uplink bytes actually transferred
                        for a round (positives upload models; every selected
                        device uploads its soft label first — stage 1).

The three reductions are jitted: a call from the host is ONE compiled
program (``jit_aggregate``, ``jit_masked_mean_tree``,
``jit_fused_aggregate`` in a profile), not one eager launch per leaf and
op; called inside a traced program (the scan engine's fold, an
aggregator's own jit) they inline.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12


@jax.jit
def masked_mean_tree(stacked_tree, sizes: jax.Array, mask: jax.Array):
    """Weighted mean over leading axis M of every leaf, weights sizes*mask.

    Low-precision leaves (bf16/f16) accumulate in float32 — summing a
    large cohort in the leaf dtype loses mass (bf16 has 8 mantissa bits)
    — and cast back on return. One compiled program for the whole tree;
    XLA may fuse each leaf's multiply into its reduction, so against the
    same ops run eagerly op by op a result can move by float32 summation
    order (<= 1.5e-07 on the paper CNN stacked x10), not bit-for-bit.
    """
    w = (jnp.asarray(sizes, jnp.float32) * jnp.asarray(mask, jnp.float32))
    tot = jnp.clip(jnp.sum(w), _EPS, None)

    def leaf(x):
        acc = jnp.promote_types(x.dtype, jnp.float32)
        wl = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(acc)
        out = jnp.sum(x.astype(acc) * wl, axis=0) / tot.astype(acc)
        return out.astype(x.dtype)

    return jax.tree.map(leaf, stacked_tree)


@partial(jax.jit, static_argnames=("backend", "block_p",
                                   "vmem_budget_bytes"))
def fused_aggregate(stacked_tree, sizes: jax.Array, mask: jax.Array,
                    *, backend: str | None = None, block_p: int = 2048,
                    vmem_budget_bytes: int = 4 * 1024 * 1024):
    """:func:`masked_mean_tree` as ONE flat reduction.

    Flattens every leaf of the stacked client pytree into a single
    ``(M, P)`` float32 buffer (P = total param count) and runs one
    weighted segment-reduce over the client axis
    (:func:`repro.kernels.ops.masked_weighted_sum`; ``backend="pallas"``
    tiles both the client and param axes through a
    ``vmem_budget_bytes``-bounded grid — LM-sized P never pins an
    (M, P) stripe in VMEM — ``"xla"``/None is the fused-jnp reference),
    then unflattens back to the leaf shapes/dtypes. The pre-flatten f32
    cast means low-precision (bf16) leaves accumulate in f32, the same
    accumulate-dtype contract as ``masked_mean_tree``. Matches
    ``masked_mean_tree`` to float32 tolerance — the reduction order over
    the flat buffer differs from the per-leaf order, so this is a
    tolerance contract, not a bitwise one.
    """
    from ..kernels import ops as kops

    leaves, treedef = jax.tree.flatten(stacked_tree)
    m = leaves[0].shape[0]
    w = (jnp.asarray(sizes, jnp.float32) * jnp.asarray(mask, jnp.float32))
    tot = jnp.clip(jnp.sum(w), _EPS, None)
    flat = jnp.concatenate(
        [x.reshape(m, -1).astype(jnp.float32) for x in leaves], axis=1)
    red = kops.masked_weighted_sum(
        flat, w, backend=backend, block_p=block_p,
        vmem_budget_bytes=vmem_budget_bytes) / tot
    outs, off = [], 0
    for x in leaves:
        n = int(np.prod(x.shape[1:], dtype=np.int64))
        outs.append(red[off:off + n].reshape(x.shape[1:]).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, outs)


@jax.jit
def aggregate(stacked_params, sizes: jax.Array, mask: jax.Array):
    """Paper Alg. 2 line 21: w_g = sum_{i in A} L_i * W_i / sum_{i in A} L_i."""
    return masked_mean_tree(stacked_params, sizes, mask)


def tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def comm_bytes(
    model_template,
    num_selected: int,
    num_positive: int,
    num_classes: int,
    soft_label_bytes_per_class: int = 4,
    control_variate: bool = False,
) -> dict:
    """Uplink communication accounting for one round.

    Stage 1: every selected device uploads a soft label (C floats).
    Stage 2: only positive devices upload models (paper's saving).
    SCAFFOLD-style optimizers double the model payload (control variates).
    """
    model_b = tree_bytes(model_template) * (2 if control_variate else 1)
    soft = num_selected * num_classes * soft_label_bytes_per_class
    models = num_positive * model_b
    return {
        "soft_label_bytes": soft,
        "model_bytes": models,
        "total_bytes": soft + models,
        "fedavg_equivalent_bytes": num_selected * model_b,
        "savings_fraction": 1.0 - (soft + models) / max(
            num_selected * model_b, 1),
    }
