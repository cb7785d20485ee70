"""Clustered federated learning: the K-center ``ModelBank`` axis.

FedEntropy screens local models against ONE global model; clustered FL
(FedGroup, arXiv 2010.06870; IFCA; FeSEM) attacks the same non-IID bias
with several concurrent group models. This module adds that axis to the
registry without forking the engines:

* :class:`ModelBank` — a stacked K-center param pytree (leading cluster
  axis). Center 0 is exactly the init params; centers 1..K-1 are
  deterministic jittered copies (seeded ``jax.random``), so K=1 IS the
  single-model seed path bit-for-bit.
* :class:`IFCAAssigner` (registry ``cluster="ifca"``) — loss-based
  assignment: one vmapped evaluation of every center on every selected
  client's local data (a (K, m) loss matrix in one jitted program), host
  ``argmin`` per client (float64 cast, lowest-index ties — deterministic
  across engines).
* :class:`FeSEMAssigner` (registry ``cluster="fesem"``) — weight-distance
  alternation: sticky per-client assignments (seeded init), re-assigned
  *after* each round by ``argmin_k ||w_i - c_k||^2`` against the
  pre-aggregation centers. Assignment is verdict-independent, which is
  what lets the pipelined engine speculate through it.

Judgment and aggregation run *within* each cluster: the server masks the
round's verdict per cluster (``Server._verdict``) and the
``perclstr`` aggregator (:mod:`repro.fl.aggregators`) averages each
center over its admitted members only, keeping empty clusters' centers
unchanged. Compositions: ``ifca``, ``ifca+maxent`` (per-cluster
max-entropy judgment — the composition no baseline has), ``fesem``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register
from .spans import fetch


@dataclass(frozen=True)
class ModelBank:
    """K stacked model centers: every leaf carries a leading cluster
    axis. Thin and immutable — engines swap whole banks per round."""
    stacked: Any          # pytree, leading axis K on every leaf
    k: int

    @classmethod
    def init(cls, params, k: int, *, seed: int = 0,
             jitter: float = 1e-2) -> "ModelBank":
        """Center 0 is ``params`` EXACTLY (the K=1 reduction); centers
        1..K-1 add seeded gaussian jitter (scale ``jitter``) so the
        loss-based assignment has distinct centers to separate."""
        if k < 1:
            raise ValueError("ModelBank needs k >= 1 centers")
        leaves, treedef = jax.tree.flatten(params)
        base = jax.random.PRNGKey(np.uint32(seed))
        centers = [leaves]
        for c in range(1, k):
            kc = jax.random.fold_in(base, c)
            jittered = []
            for i, leaf in enumerate(leaves):
                if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
                    noise = jax.random.normal(
                        jax.random.fold_in(kc, i), jnp.shape(leaf),
                        jnp.asarray(leaf).dtype)
                    jittered.append(leaf + jitter * noise)
                else:
                    jittered.append(leaf)
            centers.append(jittered)
        stacked = [jnp.stack([c[i] for c in centers])
                   for i in range(len(leaves))]
        return cls(stacked=jax.tree.unflatten(treedef, stacked), k=int(k))

    def replace(self, stacked) -> "ModelBank":
        return ModelBank(stacked=stacked, k=self.k)

    def center(self, i: int):
        """Center ``i`` as a plain (unstacked) param pytree."""
        return jax.tree.map(lambda s: s[i], self.stacked)

    def gather(self, cluster_ids):
        """Per-client start params: row ``j`` is the center assigned to
        client ``j`` — the (m, ...) stacked tree the banked client fan-out
        vmaps/shards over (in_axes 0 on the params slot)."""
        cids = jnp.asarray(np.asarray(cluster_ids), jnp.int32)
        return jax.tree.map(lambda s: jnp.take(s, cids, axis=0),
                            self.stacked)


def argmin_assign(scores) -> np.ndarray:
    """Host-deterministic per-client assignment from a (K, m) score
    matrix: float64 cast, ``argmin`` over the center axis, lowest index
    on ties — the one place both assigners' verdicts are decided, so the
    tie-break is engine-independent by construction."""
    scores = np.asarray(scores, np.float64)
    if scores.ndim != 2:
        raise ValueError(f"scores must be (K, m), got {scores.shape}")
    return np.argmin(scores, axis=0).astype(np.int64)


@register("cluster", "ifca")
class IFCAAssigner:
    """IFCA-style loss-based assignment (cluster id = argmin-loss center).

    ``bind(server)`` once at construction; ``assign(sel)`` evaluates the
    weighted cross-entropy of every center on every selected client's
    local data in one jitted ``vmap(K) x vmap(m)`` program, then picks
    per-client argmin on host. Assignment is recomputed every round from
    the current bank (``bank=`` overrides it — the pipelined engine
    assigns round t+1 against the speculatively aggregated bank).
    """

    def __init__(self, num_clusters: int):
        if num_clusters < 1:
            raise ValueError("num_clusters must be >= 1")
        self.num_clusters = int(num_clusters)
        self._server = None
        self.assign_rounds = 0

    @classmethod
    def from_config(cls, config, local):
        return cls(getattr(config, "num_clusters", 1))

    def bind(self, server) -> None:
        self._server = server

    def _loss_fn(self):
        srv = self._server
        apply_fn = srv.apply_fn

        def losses(stacked, data):
            def one_center(center):
                def one_client(x, y, w):
                    logits = apply_fn(center, x)[0].astype(jnp.float32)
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    nll = -jnp.take_along_axis(
                        logp, y[:, None].astype(jnp.int32), axis=1)[:, 0]
                    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
                return jax.vmap(one_client)(data["x"], data["y"], data["w"])
            return jax.vmap(one_center)(stacked)       # (K, m)

        return srv._compile_cache().get(
            ("ifca-assign", apply_fn, srv.corpus.signature()),
            lambda: jax.jit(losses))

    def assign(self, sel, bank: ModelBank | None = None) -> np.ndarray:
        srv = self._server
        bank = srv.bank if bank is None else bank
        data = srv.corpus.cohort(np.asarray(sel))
        scores = self._loss_fn()(bank.stacked, data)
        self.assign_rounds += 1
        return argmin_assign(fetch(scores, np.float64))

    def update(self, sel, cluster_ids, out, bank) -> None:
        """IFCA re-assigns from scratch each round; nothing to fold."""

    def stats(self) -> dict:
        return {"kind": "ifca", "num_clusters": self.num_clusters,
                "assign_rounds": self.assign_rounds}


@register("cluster", "fesem")
class FeSEMAssigner:
    """FeSEM-style weight-distance assignment with sticky memberships.

    Every client holds a persistent cluster id (seeded uniform init over
    the K centers); ``assign(sel)`` just reads it. After each round
    ``update`` re-files the participating clients by squared weight
    distance between their trained local params and the round's
    *pre-aggregation* centers — the alternating-optimization step, and
    verdict-independent, so speculation replays it exactly.
    """

    def __init__(self, num_clusters: int, num_clients: int, seed: int = 0):
        if num_clusters < 1:
            raise ValueError("num_clusters must be >= 1")
        self.num_clusters = int(num_clusters)
        self.num_clients = int(num_clients)
        rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0xFE5E]))
        self.assignments = (
            np.zeros(self.num_clients, np.int64) if self.num_clusters == 1
            else rng.integers(0, self.num_clusters, size=self.num_clients,
                              dtype=np.int64))
        self._server = None
        self.reassigned = 0

    @classmethod
    def from_config(cls, config, local):
        return cls(getattr(config, "num_clusters", 1),
                   config.num_clients, config.seed)

    def bind(self, server) -> None:
        self._server = server

    def _dist_fn(self):
        srv = self._server

        def dists(stacked, rows):
            def one_center(center):
                per_leaf = jax.tree.map(
                    lambda r, c: jnp.sum(
                        jnp.square(r.astype(jnp.float32)
                                   - c[None].astype(jnp.float32)),
                        axis=tuple(range(1, r.ndim))),
                    rows, center)
                return sum(jax.tree.leaves(per_leaf))   # (m,)
            return jax.vmap(one_center)(stacked)        # (K, m)

        return srv._compile_cache().get(
            ("fesem-dist", srv.apply_fn), lambda: jax.jit(dists))

    def assign(self, sel, bank: ModelBank | None = None) -> np.ndarray:
        return self.assignments[np.asarray(sel, np.int64)].copy()

    def update(self, sel, cluster_ids, out, bank: ModelBank) -> None:
        scores = self._dist_fn()(bank.stacked, out["params"])
        new = argmin_assign(fetch(scores, np.float64))
        idx = np.asarray(sel, np.int64)
        self.reassigned += int(np.sum(self.assignments[idx] != new))
        self.assignments[idx] = new

    def stats(self) -> dict:
        counts = np.bincount(self.assignments,
                             minlength=self.num_clusters)
        return {"kind": "fesem", "num_clusters": self.num_clusters,
                "reassigned": self.reassigned,
                "cluster_counts": [int(c) for c in counts]}
