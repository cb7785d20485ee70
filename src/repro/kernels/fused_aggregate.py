"""Fused masked weighted aggregation kernel (Pallas, TPU target).

Paper Alg. 2 line 21 runs one weighted mean per pytree leaf — for an
LM-sized model that is hundreds of small reductions per round. Here the
whole flattened parameter buffer (M clients x P params, padded to tile
multiples) streams through VMEM in (block_m, block_p) tiles, reduced
over the client axis against the (M,) weight vector in a single kernel
launch: a segment-reduce with one segment per parameter column.

The grid is 2-D, (param tiles, client tiles) with the client index
innermost: each output block is revisited across the client tiles of its
column (the revisited dim must be the fastest-varying one), zero-
initialized on the first visit (``pl.when(mi == 0)``) and accumulated in
float32 on the rest — which is what lets an LM-sized P and a large
cohort M both stay inside a fixed VMEM budget instead of forcing an
(M, block_p) resident stripe. Tile sizes derive from
``vmem_budget_bytes`` (double-buffered f32 tile + weights slice),
``block_p`` clamped to lane multiples of 128.

The weights already fold ``sizes * mask`` (masked-out clients carry
weight 0) and padding rows/columns are zero, so no in-kernel masking is
needed — padded sums are 0 and are sliced off by the caller. Low-
precision (bf16) leaves are cast to f32 by the caller *before* the
flatten, so in-kernel accumulation is always f32 — the same
accumulate-dtype contract as ``core.aggregation.masked_mean_tree``.

Validated against ref.masked_weighted_sum_reference in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

_LANE = 128


def _fused_kernel(x_ref, w_ref, out_ref):
    mi = pl.program_id(1)          # innermost: client tiles of one column

    @pl.when(mi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, bp)
    w = w_ref[...].astype(jnp.float32)            # (bm,)
    out_ref[...] += jnp.sum(x * w[:, None], axis=0)


def _plan_tiles(m: int, p: int, block_p: int,
                vmem_budget_bytes: int) -> tuple[int, int]:
    """(block_m, block_p) so a double-buffered f32 tile fits the budget."""
    bp = min(block_p, -(-p // _LANE) * _LANE)
    bp = max(_LANE, (bp // _LANE) * _LANE)

    def rows(bp_):
        return max(1, vmem_budget_bytes // (2 * 4 * bp_))

    # narrow the column tile until at least a few client rows fit
    while bp > _LANE and rows(bp) < min(m, 8):
        bp = max(_LANE, (bp // 2 // _LANE) * _LANE)
    return min(m, rows(bp)), bp


def masked_weighted_sum(
    flat: jax.Array,     # (M, P) flattened client params, float32
    weights: jax.Array,  # (M,) sizes * mask, float32
    *,
    block_p: int = 2048,
    block_m: int | None = None,
    vmem_budget_bytes: int = 4 * 1024 * 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (P,) = sum_i weights[i] * flat[i, :] in one tiled pass."""
    m, p = flat.shape
    w = jnp.asarray(weights, jnp.float32)
    bm, bp = _plan_tiles(m, max(p, 1), block_p, vmem_budget_bytes)
    if block_m is not None:
        bm = min(int(block_m), m)
    pad_p = (bp - p % bp) % bp
    pad_m = (bm - m % bm) % bm
    x = flat
    if pad_p or pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, pad_p)))
    if pad_m:
        w = jnp.pad(w, (0, pad_m))      # zero weight: padded rows sum to 0
    np_ = x.shape[1] // bp
    nm = x.shape[0] // bm

    out = pl.pallas_call(
        _fused_kernel,
        grid=(np_, nm),                 # mi innermost: out block revisited
        in_specs=[
            pl.BlockSpec((bm, bp), lambda pi, mi: (mi, pi)),
            pl.BlockSpec((bm,), lambda pi, mi: (mi,)),
        ],
        out_specs=pl.BlockSpec((bp,), lambda pi, mi: (pi,)),
        out_shape=jax.ShapeDtypeStruct((x.shape[1],), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, w)
    return out[:p]
