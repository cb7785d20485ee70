"""Maximum-entropy judgment sweep kernel (Pallas, TPU target).

THE paper's hot loop, expressed as a kernel: given per-device soft labels
P (M, C), sizes L (M,) and the active mask, compute in ONE streaming pass
over the class axis both

  * the weighted group entropy of the active set (Eq. 3/4), and
  * all M leave-one-out entropies (Alg. 1 lines 5-12, vectorized),

i.e. everything one greedy iteration of Algorithm 1 needs. The class axis
is tiled (block_c wide) so a 256k-class soft-label matrix streams through
VMEM while lane-wise entropy partial sums persist in scratch — the
judgment cost is O(M*C) per iteration with C never materialized in fp32
beyond one tile.

Every operand is a 2-D tile Mosaic can lay out: the weights ride as an
(M, 1) column (total and leave-one-out denominators are recomputed per
step from it), the group term and the M leave-one-out terms accumulate
in separate (1, block_c) / (M, block_c) scratch tiles, and each is
lane-reduced once and broadcast into a (·, 128) lane-dense output.

VMEM per step: (M, block_c) tile + (M+1, block_c) accumulators ~=
2*32*512*4 B ~= 128 KiB.

Validated against ref.entropy_judge_sweep_reference in interpret mode
and on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import resolve_interpret

_EPS = 1e-12
_LANE = 128


def _judge_kernel(p_ref, w_ref, g_ref, loo_ref, acc_g, acc_loo, *,
                  block_c: int, num_classes: int):
    ci = pl.program_id(0)
    nc = pl.num_programs(0)

    @pl.when(ci == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_loo[...] = jnp.zeros_like(acc_loo)

    p = p_ref[...].astype(jnp.float32)            # (M, bc)
    w = w_ref[...]                                # (M, 1)
    tot = jnp.sum(w, axis=0, keepdims=True)       # (1, 1)
    den = jnp.maximum(tot - w, _EPS)              # (M, 1) tot - w_k

    c_idx = ci * block_c + jax.lax.broadcasted_iota(
        jnp.int32, (p.shape[0], block_c), 1)
    valid = c_idx < num_classes
    pw = jnp.where(valid, p * w, 0.0)             # (M, bc)
    s = jnp.sum(pw, axis=0, keepdims=True)        # (1, bc) weighted sum

    def plogp(q):
        return jnp.where(q > 0, q * jnp.log(jnp.maximum(q, _EPS)), 0.0)

    # lane-wise partial sums: the cross-lane reduce runs once, at the end
    acc_g[...] -= plogp(s / jnp.maximum(tot, _EPS))
    # leave-one-out: q_k = (s - w_k p_k) / (tot - w_k)
    acc_loo[...] -= plogp((s - pw) / den)

    @pl.when(ci == nc - 1)
    def _emit():
        g_ref[...] = jnp.broadcast_to(
            jnp.sum(acc_g[...], axis=1, keepdims=True), g_ref.shape)
        loo_ref[...] = jnp.broadcast_to(
            jnp.sum(acc_loo[...], axis=1, keepdims=True), loo_ref.shape)


def entropy_judge_sweep(
    soft_labels: jax.Array,    # (M, C)
    sizes: jax.Array,          # (M,)
    mask: jax.Array,           # (M,)
    *,
    block_c: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (group_entropy (), leave_one_out (M,)) matching
    core.entropy semantics (emptying removals -> -1.0). ``interpret``
    defaults to the platform's choice (:func:`.resolve_interpret`)."""
    m, c = soft_labels.shape
    w = (jnp.asarray(sizes, jnp.float32) * jnp.asarray(mask, jnp.float32))
    tot = jnp.sum(w)

    block_c = min(block_c, c)
    pad = (block_c - c % block_c) % block_c
    p = soft_labels
    if pad:
        p = jnp.pad(p, ((0, 0), (0, pad)))
    nc = p.shape[1] // block_c

    kernel = functools.partial(_judge_kernel, block_c=block_c,
                               num_classes=c)
    g, loo = pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((m, block_c), lambda ci: (0, ci)),
            pl.BlockSpec((m, 1), lambda ci: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _LANE), lambda ci: (0, 0)),
            pl.BlockSpec((m, _LANE), lambda ci: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((m, _LANE), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32),
                        pltpu.VMEM((m, block_c), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(p, w[:, None])

    ent = g[0, 0]
    loo = jnp.where(tot - w > _EPS, loo[:, 0], -1.0)
    # empty active set -> uniform/max-entropy convention of the reference
    ent = jnp.where(tot > 0, ent, jnp.log(jnp.asarray(c, jnp.float32)))
    return ent, loo
