"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *semantics* — kernels must match them (tests sweep shapes and
dtypes and assert allclose). They are also the XLA fallback used by model
code on non-TPU backends and in the dry-run lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mha_reference(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, K, D)  K | H
    v: jax.Array,            # (B, T, K, Dv)
    *,
    causal: bool = True,
    window: int = 0,          # 0 = unlimited
    q_offset: jax.Array | int = 0,   # global position of q[0] (decode)
    kv_positions: jax.Array | None = None,  # (B, T) global pos per kv slot,
                                            # -1 = invalid (ring buffers)
    scale: float | None = None,
) -> jax.Array:
    """Grouped-query attention with causal/sliding-window masking; the
    values' head size may differ from the queries' (MLA)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = d ** -0.5 if scale is None else scale

    qq = q.reshape(b, s, kh, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qq.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale

    q_pos = jnp.arange(s)[None, :] + jnp.atleast_1d(
        jnp.asarray(q_offset)).reshape(-1, 1)                      # (1|B, S)
    if kv_positions is None:
        kv_pos = jnp.arange(t)[None, :]                            # (1, T)
        valid = jnp.ones((1, t), bool)
    else:
        kv_pos = kv_positions
        valid = kv_pos >= 0
    mask = valid[:, None, :]                                       # (B,1,T)
    if causal:
        mask = mask & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btke->bskge", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def mha_blockwise(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, K, D)
    v: jax.Array,            # (B, T, K, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: jax.Array | int = 0,
    kv_positions: jax.Array | None = None,
    scale: float | None = None,
    block_k: int = 512,
) -> jax.Array:
    """Flash-style blockwise attention in pure XLA (lax.scan over k-blocks
    with an online softmax). Numerically equivalent to ``mha_reference``
    but never materializes the (S, T) score matrix — peak attention
    activations drop from O(S*T) to O(S*block_k). Each scan step is
    rematerialized (jax.checkpoint) so the backward pass recomputes block
    scores flash-style instead of saving them.

    This is the §Perf "beyond-paper" memory optimization and doubles as
    the XLA twin of the Pallas flash_attention kernel (same math, same
    blocking), so TPU deployments get the kernel and everything else gets
    this.
    """
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = d ** -0.5 if scale is None else scale
    block_k = min(block_k, t)
    pad = (block_k - t % block_k) % block_k
    nb = (t + pad) // block_k

    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_pos = jnp.arange(s)[None, :] + jnp.atleast_1d(
        jnp.asarray(q_offset)).reshape(-1, 1)              # (1|B, S)
    if kv_positions is None:
        kv_pos_full = jnp.broadcast_to(jnp.arange(t)[None], (1, t))
    else:
        kv_pos_full = kv_positions
    kv_pad = jnp.pad(kv_pos_full, ((0, 0), (0, pad)),
                     constant_values=-1)

    qq = (q.reshape(b, s, kh, g, d).astype(jnp.float32) * scale)

    def block(carry, inp):
        m_prev, l_prev, acc = carry
        kb, vb, posb = inp                                 # (B|1? ...)
        sc = jnp.einsum("bskgd,btkd->bkgst", qq,
                        kb.astype(jnp.float32))            # (B,K,G,S,bk)
        valid = posb >= 0
        mask = valid[:, None, :]
        if causal:
            mask = mask & (posb[:, None, :] <= q_pos[:, :, None])
        if window:
            mask = mask & (posb[:, None, :] > q_pos[:, :, None] - window)
        sc = jnp.where(mask[:, None, None, :, :], sc, -1e30)
        m_cur = jnp.max(sc, axis=-1)                       # (B,K,G,S)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bskgd", p, vb.astype(jnp.float32)
        ).transpose(0, 2, 3, 1, 4)
        return (m_new, l_new, acc), None

    kb = jnp.moveaxis(kp.reshape(b, nb, block_k, kh, d), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, nb, block_k, kh, d), 1, 0)
    posb = jnp.moveaxis(
        jnp.broadcast_to(kv_pad, (b, nb * block_k)).reshape(
            b, nb, block_k), 1, 0)
    init = (jnp.full((b, kh, g, s), -1e30, jnp.float32),
            jnp.zeros((b, kh, g, s), jnp.float32),
            jnp.zeros((b, kh, g, s, d), jnp.float32))
    (m_f, l_f, acc), _ = jax.lax.scan(jax.checkpoint(block), init,
                                      (kb, vb, posb))
    out = acc / jnp.clip(l_f, 1e-30, None)[..., None]      # (B,K,G,S,D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)
    return out.astype(q.dtype)


def ssd_reference(
    x: jax.Array,        # (B, L, H, P)   inputs per head
    dt: jax.Array,       # (B, L, H)      discretization steps (post-softplus)
    a: jax.Array,        # (H,)           negative decay rates (A = -exp(A_log))
    b_mat: jax.Array,    # (B, L, G, N)   input projections ("B" of SSM)
    c_mat: jax.Array,    # (B, L, G, N)   output projections ("C")
    *,
    init_state: jax.Array | None = None,   # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Sequential (exact) SSD recurrence — the oracle for the chunked kernel.

    h_t = exp(dt_t a) h_{t-1} + dt_t * x_t outer b_t ;  y_t = h_t . c_t
    Returns (y (B,L,H,P), final_state (B,H,P,N)).
    """
    bsz, l, h, p = x.shape
    g = b_mat.shape[2]
    rep = h // g
    bh = jnp.repeat(b_mat, rep, axis=2)           # (B, L, H, N)
    ch = jnp.repeat(c_mat, rep, axis=2)
    decay = jnp.exp(dt * a[None, None, :])        # (B, L, H)

    def step(hstate, t):
        dx = (dt[:, t, :, None] * x[:, t]).astype(jnp.float32)   # (B,H,P)
        upd = dx[..., :, None] * bh[:, t, :, None, :]            # (B,H,P,N)
        hstate = decay[:, t, :, None, None] * hstate + upd
        y = jnp.einsum("bhpn,bhn->bhp", hstate, ch[:, t])
        return hstate, y

    h0 = (jnp.zeros((bsz, h, p, b_mat.shape[-1]), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    hT, ys = jax.lax.scan(step, h0, jnp.arange(l))
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)    # (B, L, H, P)
    return y, hT


def ssd_chunked_reference(
    x: jax.Array,        # (B, L, H, P)
    dt: jax.Array,       # (B, L, H)
    a: jax.Array,        # (H,)
    b_mat: jax.Array,    # (B, L, G, N)
    c_mat: jax.Array,    # (B, L, G, N)
    *,
    chunk: int = 256,
    init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel SSD (Mamba2 Sec. 6): quadratic intra-chunk part +
    sequential inter-chunk state scan. Equivalent to ``ssd_reference`` but
    O(L/Q) sequential steps instead of O(L). This is the XLA production path
    and the blueprint the Pallas kernel tiles.
    """
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    q = min(chunk, l)
    if l % q:   # pad tail with dt=0 steps (decay=1, zero update): exact
        pad = q - l % q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, h_t = ssd_chunked_reference(x, dt, a, b_mat, c_mat, chunk=q,
                                       init_state=init_state)
        return y[:, :l], h_t
    c = l // q
    rep = h // g
    bh = jnp.repeat(b_mat, rep, axis=2).reshape(bsz, c, q, h, n)
    ch = jnp.repeat(c_mat, rep, axis=2).reshape(bsz, c, q, h, n)
    xg = x.reshape(bsz, c, q, h, p)
    dtg = dt.reshape(bsz, c, q, h).astype(jnp.float32)
    adt = dtg * a[None, None, None, :]                     # log decays
    cums = jnp.cumsum(adt, axis=2)                          # (B,C,Q,H)

    # ---- intra-chunk (quadratic) --------------------------------------
    seg = cums[:, :, :, None, :] - cums[:, :, None, :, :]   # (B,C,Q,Q,H)
    causal = jnp.tril(jnp.ones((q, q), bool))
    lmat = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    dtx = dtg[..., None] * xg.astype(jnp.float32)           # (B,C,Q,H,P)
    cb = jnp.einsum("bcqhn,bckhn->bcqkh", ch.astype(jnp.float32),
                    bh.astype(jnp.float32))
    y_diag = jnp.einsum("bcqkh,bckhp->bcqhp", cb * lmat, dtx)

    # ---- chunk summary states ------------------------------------------
    decay_to_end = jnp.exp(cums[:, :, -1:, :] - cums)       # (B,C,Q,H)
    states = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn", decay_to_end,
                        bh.astype(jnp.float32), dtx)        # (B,C,H,P,N)
    chunk_decay = jnp.exp(cums[:, :, -1, :])                # (B,C,H)

    # ---- inter-chunk recurrence (sequential over C chunks) --------------
    def step(hstate, inp):
        s, dec = inp
        prev = hstate
        hstate = dec[..., None, None] * hstate + s
        return hstate, prev

    h0 = (jnp.zeros((bsz, h, p, n), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    h_t, h_prevs = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                   # (B,C,H,P,N)

    # ---- inter-chunk contribution ----------------------------------------
    decay_in = jnp.exp(cums)                                # (B,C,Q,H)
    y_off = jnp.einsum("bcqh,bcqhn,bchpn->bcqhp", decay_in,
                       ch.astype(jnp.float32), h_prevs)
    y = (y_diag + y_off).reshape(bsz, l, h, p).astype(x.dtype)
    return y, h_t


def entropy_judge_sweep_reference(
    soft_labels: jax.Array,   # (M, C)
    sizes: jax.Array,         # (M,)
    mask: jax.Array,          # (M,)
) -> tuple[jax.Array, jax.Array]:
    """(group_entropy, leave-one-out entropies (M,)) — oracle for the
    entropy_judge kernel; mirrors core.entropy.leave_one_out_entropies."""
    from ..core.entropy import group_entropy, leave_one_out_entropies
    return (group_entropy(soft_labels, sizes, mask),
            leave_one_out_entropies(soft_labels, sizes, mask))


def masked_weighted_sum_reference(
    flat: jax.Array,      # (M, P)
    weights: jax.Array,   # (M,)
) -> jax.Array:
    """(P,) = sum_i weights[i] * flat[i, :] — oracle for the fused
    aggregation kernel (one fused-jnp reduction over the client axis)."""
    w = jnp.asarray(weights, jnp.float32)
    return jnp.sum(flat.astype(jnp.float32) * w[:, None], axis=0)


def gmm_reference(lhs: jax.Array, rhs: jax.Array,
                  group_sizes: jax.Array) -> jax.Array:
    """Grouped matrix product: rows ``[o_g, o_g + group_sizes[g])`` of
    ``lhs`` (R, K), with ``o_g`` the sizes of the groups before g, times
    ``rhs[g]`` (G, K, N); rows past the last group are zero. One masked
    dense product per group."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        mine = (row >= ends[g] - group_sizes[g]) & (row < ends[g])
        out = out + jnp.where(mine, lhs @ rhs[g], 0.0)
    return out.astype(lhs.dtype)
