"""Judge implementations: which selected devices' models aggregate.

``MaxEntropyJudge``   — the paper's Algorithm 1 (greedy removal maximising
                        size-weighted group entropy). ``backend=`` picks the
                        implementation: ``"numpy"`` (default) is the float64
                        oracle the legacy trainer used; ``"xla"`` and
                        ``"pallas"`` route through the traced
                        ``core.judgment.judge`` — the latter tiles the class
                        axis through the Pallas ``entropy_judge_sweep``
                        kernel for huge C.
``PassThroughJudge``  — admits everyone (the ``use_judgment=False``
                        ablation / plain FedAvg-of-selected).
``BudgetedJudge``     — beyond-paper forward-greedy selection of exactly
                        ``budget`` devices (``core.judgment.judge_budgeted``)
                        for deployments with a hard per-round uplink cap.

All return ``(accepted, rejected, entropy)`` with *relative* indices into
the round's selection (see ``protocols.Judge``); rejected indices are in
greedy-removal order for every backend. Judges additionally expose
``traced()`` — a jit-compatible callable returning a
``core.judgment.JudgmentResult`` — which is how the mesh train step
(``repro.launch.train``) and the pipelined engine's speculation
(``repro.fl.runtime``) run the same judge axis on device.

The async buffered engine screens *arriving* updates instead of whole
rounds: ``MaxEntropyJudge.admit`` judges candidates against the
already-admitted (protected) buffer, and :func:`admit_candidates` adapts
any plain round judge to the same candidate-relative admission signature.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.judgment import (
    JudgmentResult, judge, judge_budgeted, judge_np,
)
from .registry import register
from .spans import fetch


def _result_to_lists(res: JudgmentResult
                     ) -> tuple[list[int], list[int], float]:
    mask = fetch(res.mask)
    accepted = [i for i in range(len(mask)) if mask[i] > 0]
    if res.removal_order is not None:
        rejected = [int(k) for k in fetch(res.removal_order) if k >= 0]
    else:
        rejected = [i for i in range(len(mask)) if mask[i] == 0]
    return accepted, rejected, float(fetch(res.entropy))


def _stack_buffer(buffer_soft, buffer_sizes, cand_soft, cand_sizes):
    """Concatenate (buffer, candidates) as float64; nb==0 passes the
    candidate arrays through untouched so admission over an empty buffer
    is bit-for-bit the plain round judgment (the async engine's reduction
    guarantee rides on this)."""
    cand_soft = np.asarray(cand_soft, np.float64)
    cand_sizes = np.asarray(cand_sizes, np.float64)
    nb = int(np.shape(buffer_sizes)[0])
    if nb == 0:
        return 0, cand_soft, cand_sizes
    soft = np.concatenate(
        [np.asarray(buffer_soft, np.float64), cand_soft], axis=0)
    sizes = np.concatenate(
        [np.asarray(buffer_sizes, np.float64), cand_sizes], axis=0)
    return nb, soft, sizes


def admit_candidates(judge_obj, buffer_soft, buffer_sizes, cand_soft,
                     cand_sizes) -> tuple[list[int], list[int], float]:
    """Admission fallback for judges without an ``admit`` method.

    Runs the judge once over buffer ∪ candidates and reads the verdicts
    for the candidate rows only (*relative* to the candidate block;
    rejected in removal order). Buffered rows have already shipped their
    weights, so a verdict against one of them is ignored here — judges
    that must never "re-litigate" the buffer implement ``admit`` with a
    protected sweep instead (see :meth:`MaxEntropyJudge.admit`).
    """
    nb, soft, sizes = _stack_buffer(buffer_soft, buffer_sizes,
                                    cand_soft, cand_sizes)
    accepted, rejected, ent = judge_obj(soft, sizes)
    return ([i - nb for i in accepted if i >= nb],
            [i - nb for i in rejected if i >= nb], ent)


@register("judge", "maxent")
class MaxEntropyJudge:
    """Paper Algorithm 1: drop devices whose removal raises group entropy.

    backend: "numpy" (float64 host oracle), "xla" (traced float32
    leave-one-out sweep) or "pallas" (class-axis-tiled kernel).
    """

    def __init__(self, backend: str = "numpy"):
        if backend not in ("numpy", "xla", "pallas"):
            raise ValueError(f"unknown judge backend {backend!r}")
        self.backend = backend
        self._jitted = None       # compiled host-call path, built lazily
        self._jitted_admit = None  # compiled protected-sweep path (async)

    def __call__(self, soft_labels: np.ndarray, sizes: np.ndarray
                 ) -> tuple[list[int], list[int], float]:
        if self.backend == "numpy":
            return judge_np(soft_labels, sizes)
        if self._jitted is None:  # don't re-trace the while_loop per round
            self._jitted = jax.jit(self.traced())
        res = self._jitted(jnp.asarray(soft_labels, jnp.float32),
                           jnp.asarray(sizes, jnp.float32))
        return _result_to_lists(res)

    def traced(self):
        """Jit-compatible (soft_labels, sizes) -> JudgmentResult; numpy
        backend falls back to the xla sweep (same greedy, float32)."""
        backend = "xla" if self.backend == "numpy" else self.backend
        return lambda soft, sizes: judge(soft, sizes, backend=backend)

    def admit(self, buffer_soft, buffer_sizes, cand_soft, cand_sizes
              ) -> tuple[list[int], list[int], float]:
        """Per-arrival admission for the async engine: Algorithm 1's greedy
        removal over buffer ∪ candidates, with the buffered rows *protected*
        — they contribute to the group entropy (their weights already
        shipped) but are never removal candidates. Returns
        ``(admitted, rejected, entropy)`` relative to the candidate block,
        rejected in removal order; with an empty buffer this is exactly the
        round judgment ``__call__`` runs, which is what makes the
        K=|cohort| zero-latency reduction bit-for-bit.
        """
        nb, soft, sizes = _stack_buffer(buffer_soft, buffer_sizes,
                                        cand_soft, cand_sizes)
        if nb == 0:
            return self(soft, sizes)
        if self.backend == "numpy":
            prot = np.zeros(len(sizes))
            prot[:nb] = 1.0
            accepted, rejected, ent = judge_np(soft, sizes, protected=prot)
        else:
            if self._jitted_admit is None:
                backend = self.backend
                self._jitted_admit = jax.jit(
                    lambda s, z, p: judge(s, z, backend=backend,
                                          protected=p))
            prot = jnp.zeros((len(sizes),), jnp.float32).at[:nb].set(1.0)
            res = self._jitted_admit(jnp.asarray(soft, jnp.float32),
                                     jnp.asarray(sizes, jnp.float32), prot)
            accepted, rejected, ent = _result_to_lists(res)
        return ([i - nb for i in accepted if i >= nb],
                [i - nb for i in rejected if i >= nb], ent)


@register("judge", "none")
class PassThroughJudge:
    """Admit every selected device; entropy is not defined (NaN)."""

    def __call__(self, soft_labels: np.ndarray, sizes: np.ndarray
                 ) -> tuple[list[int], list[int], float]:
        return list(range(len(sizes))), [], float("nan")

    def traced(self):
        def all_in(soft, sizes):
            m = soft.shape[0]
            ones = jnp.ones((m,), jnp.float32)
            nan = jnp.full((), jnp.nan, jnp.float32)
            return JudgmentResult(
                mask=ones, entropy=nan, initial_entropy=nan,
                num_removed=jnp.zeros((), jnp.int32),
                removal_order=jnp.full((m,), -1, jnp.int32))
        return all_in


@register("judge", "budget")
class BudgetedJudge:
    """Keep exactly ``budget`` devices, forward-greedy on group entropy."""

    def __init__(self, budget: int):
        self.budget = int(budget)

    @classmethod
    def from_config(cls, config, local):
        raise ValueError(
            "BudgetedJudge needs an explicit budget — pass an instance, "
            "e.g. build(..., judge=BudgetedJudge(budget=3))")

    def __call__(self, soft_labels: np.ndarray, sizes: np.ndarray
                 ) -> tuple[list[int], list[int], float]:
        res = judge_budgeted(jnp.asarray(soft_labels, jnp.float32),
                             jnp.asarray(sizes, jnp.float32), self.budget)
        return _result_to_lists(res)

    def traced(self):
        budget = self.budget
        return lambda soft, sizes: judge_budgeted(soft, sizes, budget)
