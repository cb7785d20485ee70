"""Jit'd public wrappers for the kernel package with backend dispatch.

backend="xla"     — pure-jnp reference implementations (CPU, dry-run).
backend="pallas"  — Pallas TPU kernels: Mosaic-compiled on a TPU, run by
                    the Pallas interpreter on any other backend
                    (:func:`._platform.resolve_interpret`).

``set_default_backend`` flips the global default (used by tests and by the
launcher's --kernels flag).
"""
from __future__ import annotations

import functools

import jax

from . import ref

_DEFAULT = "xla"


def set_default_backend(name: str) -> None:
    global _DEFAULT
    assert name in ("xla", "pallas", "blockwise")
    _DEFAULT = name


def attention(q, k, v, *, causal=True, window=0, q_offset=0,
              kv_positions=None, scale=None, backend=None):
    backend = backend or _DEFAULT
    if backend == "pallas" and q.shape[1] == 1 and kv_positions is not None:
        from .decode_attention import decode_attention
        import jax.numpy as jnp
        idx = jnp.max(kv_positions)   # current position = newest slot tag
        return decode_attention(q, k, v, kv_positions, idx, window=window,
                                scale=scale)
    if backend == "pallas" and q.shape[1] > 1:
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale)
    if backend == "blockwise" and k.shape[1] > 512:
        return ref.mha_blockwise(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset,
                                 kv_positions=kv_positions, scale=scale)
    return ref.mha_reference(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, kv_positions=kv_positions,
                             scale=scale)


def ssd(x, dt, a, b_mat, c_mat, *, chunk=256, init_state=None, backend=None):
    backend = backend or _DEFAULT
    if backend == "pallas":
        from .ssd_scan import ssd_chunked
        return ssd_chunked(x, dt, a, b_mat, c_mat, chunk=chunk,
                           init_state=init_state)
    if x.shape[1] == 1:   # single-token: exact sequential step
        return ref.ssd_reference(x, dt, a, b_mat, c_mat,
                                 init_state=init_state)
    return ref.ssd_chunked_reference(x, dt, a, b_mat, c_mat, chunk=chunk,
                                     init_state=init_state)


def entropy_judge_sweep(soft_labels, sizes, mask, *, backend=None):
    backend = backend or _DEFAULT
    if backend == "pallas":
        from .entropy_judge import entropy_judge_sweep
        return entropy_judge_sweep(soft_labels, sizes, mask)
    return ref.entropy_judge_sweep_reference(soft_labels, sizes, mask)


def masked_weighted_sum(flat, weights, *, backend=None, block_p=2048,
                        vmem_budget_bytes=4 * 1024 * 1024):
    backend = backend or _DEFAULT
    if backend == "pallas":
        from .fused_aggregate import masked_weighted_sum
        return masked_weighted_sum(
            flat, weights, block_p=block_p,
            vmem_budget_bytes=vmem_budget_bytes)
    return ref.masked_weighted_sum_reference(flat, weights)


def gmm(lhs, rhs, group_sizes, *, backend=None):
    """Grouped matrix product (``ref.gmm_reference``): ``lhs`` (R, K) rows
    sorted by group, ``rhs`` (G, K, N), ``group_sizes`` (G,) int32 summing
    to at most R; rows past the groups come out zero.

    ``pallas`` is the megablox ``gmm`` kernel (its rows padded to its
    128-row tile), the default on a TPU: on a v5e, in 128x128x128 tiles,
    it ran a 4-expert-layer Moonlight step in 169 ms against 659 ms for
    ``jax.lax.ragged_dot``'s TPU lowering. Its tiles follow each
    product's shape (``gmm_tiles``), in the forward and in both
    gradients: the cell's 5-expert-layer step spends 9.2 ms a step in
    them, against 55-64 ms in 128x128x128 tiles. ``xla`` is
    ``jax.lax.ragged_dot``, the default elsewhere.
    Neither writes the rows past the groups on a TPU, in the product or
    in its gradient of ``lhs``: selects on both sides keep them out.

    Where ``lhs`` is typed with a mesh (``launch.mesh.make_host_mesh``'s
    axes are Explicit), neither has a sharding rule: the product runs in
    ``shard_map`` with every operand whole on each device."""
    import jax.numpy as jnp
    from ._platform import target_platform
    if backend is None:
        backend = "pallas" if target_platform() == "tpu" else _DEFAULT
    fn = _megablox_gmm if backend == "pallas" else jax.lax.ragged_dot
    mesh = jax.typeof(lhs).sharding.mesh
    if not mesh.empty:
        from jax.sharding import PartitionSpec as P
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(), P(), P()),
                           out_specs=P(), check_vma=False)
    valid = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    return jnp.where(valid, fn(jnp.where(valid, lhs, 0), rhs, group_sizes),
                     0)


# Megablox sets no ``vmem_limit_bytes``: a product's tiles live in
# Mosaic's default scoped VMEM on a v5e and stay under this (a 14.7 MB
# ``gmm`` footprint compiles there, a 19.8 MB ``tgmm`` one does not).
_GMM_VMEM_BYTES = 12 * 2**20
_GMM_TM = 128


def _gmm_dim_tiles(d):
    """Legal tiles of a product dimension: the whole of it, or a multiple
    of 128 that divides it (``tgmm`` drops a k remainder)."""
    return [d] + [t for t in range(128, d, 128) if d % t == 0]


def gmm_tiles(m, k, n, itemsize):
    """Megablox tiles ``(tm, tk, tn)`` for a grouped product of ``m``
    rows, contracting ``k`` into ``n`` (``tgmm``: contracting the rows).

    Every product holds a (tm, tk), a (tk, tn) and a (tm, tn) tile,
    double-buffered, and an f32 accumulator of (tm, tn) (``gmm``) or
    (tk, tn) (``tgmm``, called with the same shape). Of the legal (tk, tn)
    whose footprint fits ``_GMM_VMEM_BYTES``, the largest tile wins, the
    longer k on a tie: fewer grid steps, and each rhs tile fetched once
    per m-tile. On a v5e, f32, the Moonlight cell's 8 experts and ~1,400
    skewed rows, (128, 128, 128) ran ~3,000 grid steps a product at
    ~0.45 us each: 1.39-1.95 ms for each of ``gmm``, its rows' gradient
    and ``tgmm``. These tiles, (128, 512, 1408) for 2048 -> 1408 and
    (128, 1408, 512) for 1408 -> 2048, took 0.36, 0.37, 0.29 and 0.26,
    0.26, 0.29 ms. ``tm`` stays 128: 256 saved ~12% of the products
    there, but pads each group's last tile to 256 rows, a loss where
    groups are many and small."""
    del m       # the rows are padded to tm
    tm = _GMM_TM

    def footprint(tk, tn):
        return (2 * itemsize * (tm * tk + tk * tn + tm * tn)
                + 4 * max(tm, tk) * tn)
    pairs = [(tk, tn) for tk in _gmm_dim_tiles(k) for tn in _gmm_dim_tiles(n)]
    tk, tn = max((p for p in pairs if footprint(*p) <= _GMM_VMEM_BYTES),
                 key=lambda p: (p[0] * p[1], p[0]))
    return tm, tk, tn


@functools.cache
def _gmm_tiling(itemsize):
    """``gmm_tiles`` for one operand width, as the ``(m, k, n)`` function
    megablox calls once per product (its forward and both gradients). A
    static argument: one object per width keeps the step's trace and
    compile-cache key stable."""
    return functools.partial(gmm_tiles, itemsize=itemsize)


def _megablox_gmm(lhs, rhs, group_sizes):
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm as mb_gmm
    from ._platform import resolve_interpret
    r = lhs.shape[0]
    out = mb_gmm(jnp.pad(lhs, ((0, -r % _GMM_TM), (0, 0))), rhs, group_sizes,
                 lhs.dtype, _gmm_tiling(lhs.dtype.itemsize), None, None,
                 False, resolve_interpret(None))
    return out[:r]
