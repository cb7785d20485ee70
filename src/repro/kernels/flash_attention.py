"""Blockwise (flash) attention Pallas kernel — TPU target.

Online-softmax attention with causal and sliding-window masking and GQA
(q-head -> kv-head map folded into the BlockSpec index maps). Grid is
(batch, q_heads, q_blocks, k_blocks); the innermost k dimension executes
sequentially on TPU, so the running max / normalizer / accumulator live in
VMEM scratch across k iterations (MaxText-style). Block shapes are
MXU-aligned (block_q x head_dim and block_k x head_dim tiles in VMEM);
with block_q = block_k = 128 and head_dim <= 256 the working set is
~(2*128*256 + 128*128) * 4 B < 1 MiB — far inside the ~16 MiB VMEM budget,
leaving room for double buffering.

Validated against kernels.ref.mha_reference in interpret mode (tests sweep
shapes, dtypes, GQA ratios, windows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = q @ k.T                                          # (bq, bk)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq,)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    # renormalize previous accumulator
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.clip(l, 1e-30, None)[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,           # (B, S, H, D)
    k: jax.Array,           # (B, T, KH, D)
    v: jax.Array,           # (B, T, KH, D)
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = d ** -0.5 if scale is None else scale
    block_q = min(block_q, s)
    block_k = min(block_k, t)

    # pad seq dims to block multiples (masked out inside the kernel)
    s_pad = (block_q - s % block_q) % block_q
    t_pad = (block_k - t % block_k) % block_k
    qt = jnp.moveaxis(q, 2, 1)                           # (B, H, S, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if s_pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    nq = qt.shape[2] // block_q
    nk = kt.shape[2] // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=t)
    out = _call(kernel, qt, kt, vt, b, h, nq, nk, block_q,
                block_k, d, g, q.dtype, interpret)
    if s_pad:
        out = out[:, :, :s, :]
    return jnp.moveaxis(out, 1, 2)


def _call(kernel, qt, kt, vt, b, h, nq, nk, block_q, block_k, d, g,
          dtype, interpret):
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
