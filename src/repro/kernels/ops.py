"""Jit'd public wrappers for the kernel package with backend dispatch.

backend="xla"     — pure-jnp reference implementations (CPU, dry-run).
backend="pallas"  — Pallas TPU kernels: Mosaic-compiled on a TPU, run by
                    the Pallas interpreter on any other backend
                    (:func:`._platform.resolve_interpret`).

``set_default_backend`` flips the global default (used by tests and by the
launcher's --kernels flag).
"""
from __future__ import annotations


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
