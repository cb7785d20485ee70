"""Single-query (decode) flash attention Pallas kernel — TPU target.

The decode hot spot: one new token attends to a long position-tagged KV
cache (ring buffers carry slot tags; -1 = empty). Grid (batch, q_heads,
k_blocks): the k axis streams cache blocks of (block_k, head_dim) through
VMEM while the online-softmax accumulator for the single query row lives
in scratch — HBM traffic is exactly one pass over the cache, which is the
roofline lower bound for decode.

Validated against ref.mha_reference (S=1) in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, tag_ref, idx_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, window: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32) * scale     # (d,)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    tags = tag_ref[0]                                  # (bk,) int32
    index = idx_ref[0]                                 # () current position

    s = k @ q                                          # (bk,)
    mask = (tags >= 0) & (tags <= index)
    if window:
        mask &= tags > index - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[0] = alpha * l_ref[0] + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0, 0] = (acc_ref[...] / jnp.clip(
            l_ref[0], 1e-30, None)).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,             # (B, 1, H, D)
    k: jax.Array,             # (B, T, KH, D) cache
    v: jax.Array,             # (B, T, KH, D)
    kv_positions: jax.Array,  # (B, T) int32 slot tags, -1 = empty
    index: jax.Array,         # () int32 current decode position
    *,
    window: int = 0,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    b, _, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = d ** -0.5 if scale is None else scale
    block_k = min(block_k, t)
    pad = (block_k - t % block_k) % block_k
    nk = (t + pad) // block_k

    kt = jnp.moveaxis(k, 2, 1)                          # (B, KH, T, D)
    vt = jnp.moveaxis(v, 2, 1)
    qt = jnp.moveaxis(q, 2, 1)                          # (B, H, 1, D)
    tags = kv_positions
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        tags = jnp.pad(tags, ((0, 0), (0, pad)), constant_values=-1)

    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(_decode_kernel, scale=scale, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, block_k), lambda bi, hi, ki: (bi, ki)),
            pl.BlockSpec((1,), lambda bi, hi, ki: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt, tags, jnp.asarray(index, jnp.int32)[None])
    return jnp.moveaxis(out, 1, 2)                      # (B, 1, H, D)
