"""Jit'd public wrappers for the kernel package with backend dispatch.

backend="xla"     — pure-jnp reference implementations (CPU, dry-run).
backend="pallas"  — Pallas TPU kernels: Mosaic-compiled on a TPU, run by
                    the Pallas interpreter on any other backend
                    (:func:`._platform.resolve_interpret`).

``set_default_backend`` flips the global default (used by tests and by the
launcher's --kernels flag).
"""
from __future__ import annotations

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
    128-row tile), the default on a TPU: on a v5e it ran the Moonlight
    cell's step in 169 ms against 659 ms for ``jax.lax.ragged_dot``'s TPU
    lowering. ``xla`` is ``jax.lax.ragged_dot``, the default elsewhere.
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


def _megablox_gmm(lhs, rhs, group_sizes):
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm as mb_gmm
    from ._platform import resolve_interpret
    r = lhs.shape[0]
    out = mb_gmm(jnp.pad(lhs, ((0, -r % 128), (0, 0))), rhs, group_sizes,
                 lhs.dtype, (128, 128, 128), None, None, False,
                 resolve_interpret(None))
    return out[:r]
