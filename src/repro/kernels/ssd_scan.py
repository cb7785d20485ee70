"""Mamba2 SSD chunk kernel (Pallas, TPU target).

One grid step processes one (batch, head, chunk) tile entirely in VMEM:
intra-chunk quadratic part, inter-chunk state contribution, and the running
state update. The chunk axis is the innermost grid dimension — TPU executes
it sequentially, so the (P, N) recurrent state lives in VMEM scratch across
chunk iterations (the inter-chunk scan is thereby FUSED into the kernel
instead of being a separate lax.scan at the ops layer).

VMEM working set per step: x (Q,P) + b,c (Q,N) + L (Q,Q) + state (P,N) in
f32 ~= (256*64 + 2*256*128 + 256^2 + 64*128) * 4 B ~= 0.6 MiB with the
default Q=256, P=64, N=128 — MXU-aligned and far inside budget.

Validated against ref.ssd_reference (sequential oracle) in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._platform import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
                h_ref, *, chunk: int, seq_len: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q,)
    a = a_ref[0].astype(jnp.float32)             # ()
    bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)

    # mask padded tail steps: dt=0 -> decay 1, zero update
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk,), 0)
    valid = (ci * chunk + q_idx) < seq_len
    dt = jnp.where(valid, dt, 0.0)

    adt = dt * a                                  # (Q,) log-decays
    cums = jnp.cumsum(adt)                        # (Q,)

    # intra-chunk: L[i,j] = exp(cums_i - cums_j) for j <= i
    seg = cums[:, None] - cums[None, :]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >=
              jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    lmat = jnp.where(causal, jnp.exp(seg), 0.0)
    dtx = dt[:, None] * x                         # (Q, P)
    cb = cm @ bm.T                                # (Q, Q) scores
    y = (cb * lmat) @ dtx                         # (Q, P)

    # inter-chunk contribution from the carried state
    h = h_ref[...]                                # (P, N)
    y += jnp.exp(cums)[:, None] * (cm @ h.T)

    # state update: h' = exp(cums[-1]) h + sum_j exp(cums[-1]-cums_j) dtx_j b_j
    decay_to_end = jnp.exp(cums[-1] - cums)       # (Q,)
    h_ref[...] = jnp.exp(cums[-1]) * h + \
        (decay_to_end[:, None] * dtx).T @ bm      # (P, N)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_ref[0, 0] = h_ref[...]


def ssd_chunked(
    x: jax.Array,        # (B, L, H, P)
    dt: jax.Array,       # (B, L, H)   post-softplus
    a: jax.Array,        # (H,)
    b_mat: jax.Array,    # (B, L, G, N)
    c_mat: jax.Array,    # (B, L, G, N)
    *,
    chunk: int = 256,
    init_state=None,     # kernel path requires zero init (assert below)
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    assert init_state is None, "ssd_chunked kernel assumes zero init state"
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    q = min(chunk, l)
    pad = (q - l % q) % q
    nc = (l + pad) // q

    xt = jnp.moveaxis(x, 2, 1)                        # (B, H, L, P)
    dtt = jnp.moveaxis(dt, 2, 1)                      # (B, H, L)
    bt = jnp.moveaxis(b_mat, 2, 1)                    # (B, G, L, N)
    ct = jnp.moveaxis(c_mat, 2, 1)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, 0), (0, pad)))
        bt = jnp.pad(bt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ct = jnp.pad(ct, ((0, 0), (0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_ssd_kernel, chunk=q, seq_len=l)
    from jax.experimental.pallas import tpu as pltpu
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, 1, q, n),
                         lambda bi, hi, ci: (bi, hi // rep, ci, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda bi, hi, ci: (bi, hi // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xt.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xt, dtt, a, bt, ct)
    if pad:
        y = y[:, :, :l, :]
    return jnp.moveaxis(y, 1, 2), state
