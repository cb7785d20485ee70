"""Selector implementations: who is asked to train this round.

``PoolSelector``    — the paper's epsilon-greedy positive/negative pools
                      (Alg. 2 lines 4-8/22), delegating to
                      ``core.pools.DevicePools``.
``UniformSelector`` — uniform sampling without replacement (the
                      ``use_pools=False`` ablation of Fig. 3b). Seeded with
                      ``seed + 1`` by the registry to match the legacy
                      trainer's RNG stream exactly.
``TracedPoolSelector`` — the same eps-greedy pool semantics driven by a
                      ``jax.random`` stream (``core.pools.pools_draw`` /
                      ``pools_refile``), so the draw can ALSO run inside
                      the scan engine's ``lax.scan`` as a device-resident
                      carry: ``engine="scan"`` folds R>1 rounds of the
                      paper's fedentropy composition instead of falling
                      back to sequential rounds. Not RNG-stream-compatible
                      with the numpy ``PoolSelector`` (histories are
                      reproducible per seed, not golden-comparable).
``CatGrouper``      — FedCAT (arXiv 2202.12751) device grouping layered
                      over an inner selector: WHO trains is delegated, and
                      the selection is additionally packed into ordered
                      groups via ``core.pools.greedy_entropy_groups``;
                      ``catgroups`` wraps ``uniform`` (plain fedcat),
                      ``catgroups-pools`` wraps ``pools`` (fedcat+maxent).
``QueueSelector``   — entropy-driven participant selection with dynamic
                      data queues (arXiv 2410.17792): clients are ranked
                      by label-distribution entropy off the bound corpus
                      stats, eps-greedy explored, and each round releases
                      a growing prefix of every selected client's local
                      dataset via a ``DataQueue`` schedule that the server
                      applies inside the cohort gather.

Selectors that consume corpus statistics implement ``bind_data`` — the
server passes its data plane (device-resident
:class:`repro.data.corpus.ClientCorpus` or streaming
:class:`repro.data.stream.HostCorpus`; the stats surface is duck-typed,
so either plane binds transparently), whose cached
``label_histograms()``/``sizes()`` replace the per-selector recompute
(a raw stacked dict still binds, for direct construction in tests).
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.pools import (
    DevicePools, greedy_entropy_groups, hist_entropy, label_histograms,
    pools_draw,
)
from ..data.corpus import DataQueue
from .registry import register
from .spans import fetch


def _corpus_histograms(client_data) -> np.ndarray:
    """Label histograms from either corpus plane (cached, duck-typed) or
    a raw stacked dict."""
    cached = getattr(client_data, "label_histograms", None)
    if cached is not None:
        return cached()
    return label_histograms(np.asarray(client_data["y"]),
                            np.asarray(client_data["w"])
                            if "w" in client_data else None)


@register("selector", "pools")
class PoolSelector:
    """Epsilon-greedy over the paper's positive/negative device pools."""

    def __init__(self, num_clients: int, eps: float = 0.8, seed: int = 0):
        self.pools = DevicePools(num_clients, eps, seed)

    @classmethod
    def from_config(cls, config, local):
        return cls(config.num_clients, config.eps, config.seed)

    def select(self, num: int) -> list[int]:
        # clamp to the population like UniformSelector/QueueSelector do,
        # so the Selector surface owns the oversized-draw contract
        # (DevicePools guards internally too, but a config with
        # participation * num_clients > num_clients shouldn't depend on
        # that implementation detail)
        num = min(num, self.pools.num_devices)
        return self.pools.select(num)

    def update(self, positives: Sequence[int],
               negatives: Sequence[int]) -> None:
        self.pools.update(list(positives), list(negatives))

    def stats(self) -> dict:
        return self.pools.stats()


@register("selector", "pools-traced")
class TracedPoolSelector:
    """Epsilon-greedy pools on a ``jax.random`` stream — the scan-foldable
    twin of :class:`PoolSelector`.

    Selection semantics are the paper's (Alg. 2 lines 4-8/22: eps-greedy
    pool pick with spillover, cohort removed for the round, re-filed by
    verdict), but the draw is the pure jitted
    :func:`repro.core.pools.pools_draw` over (key, membership masks) —
    state the scan engine can carry on device through an R-round
    ``lax.scan``. Sequentially, :meth:`select`/:meth:`update` drive the
    identical jitted program one round at a time, so a folded block and
    the sequential ``Server`` produce bit-for-bit equal selection streams.

    The scan engine's fold surface:

    * :meth:`fold_carry` — the (key, pos_mask, neg_mask) device carry a
      block starts from;
    * :meth:`fold_drawn` — mirror one in-scan draw (cohort leaves the
      pools, the post-draw key is adopted); the engine then confirms the
      round with a normal :meth:`update`, exactly the sequential
      select/update cycle.
    """

    def __init__(self, num_clients: int, eps: float = 0.8, seed: int = 0):
        self.num_clients = int(num_clients)
        self.eps = float(eps)
        self._key = jax.random.PRNGKey(seed)
        self.positive: set[int] = set(range(self.num_clients))
        self.negative: set[int] = set()

    @classmethod
    def from_config(cls, config, local):
        return cls(config.num_clients, config.eps, config.seed)

    # ---- membership masks (the device representation) -------------------
    def _masks(self) -> tuple[jax.Array, jax.Array]:
        pos = np.zeros(self.num_clients, np.float32)
        neg = np.zeros(self.num_clients, np.float32)
        pos[sorted(self.positive)] = 1.0
        neg[sorted(self.negative)] = 1.0
        return jnp.asarray(pos), jnp.asarray(neg)

    def select(self, num: int) -> list[int]:
        num = min(num, self.num_clients)
        pos, neg = self._masks()
        sel, self._key = pools_draw(self._key, pos, neg,
                                    num=num, eps=self.eps)
        chosen = [int(c) for c in fetch(sel)]
        for c in chosen:            # removed for the round, like DevicePools
            self.positive.discard(c)
            self.negative.discard(c)
        return chosen

    def update(self, positives: Sequence[int],
               negatives: Sequence[int]) -> None:
        self.positive.update(int(i) for i in positives)
        self.negative.update(int(i) for i in negatives)

    # ---- scan-engine fold surface ---------------------------------------
    def fold_carry(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """(key, pos_mask, neg_mask) for the scan carry — the exact state
        the next sequential :meth:`select` would draw from."""
        pos, neg = self._masks()
        return self._key, pos, neg

    def fold_drawn(self, sel, key_after) -> None:
        """Mirror an in-scan draw the engine confirmed (or is about to
        replay eagerly): the cohort leaves both pools and the selector's
        key advances to the post-draw key stacked in the scan's ys."""
        for c in np.asarray(sel):
            self.positive.discard(int(c))
            self.negative.discard(int(c))
        self._key = jnp.asarray(key_after)

    def stats(self) -> dict:
        return {"selector": "pools-traced",
                "positive": len(self.positive),
                "negative": len(self.negative)}


@register("selector", "uniform")
class UniformSelector:
    """Uniform sampling without replacement; ignores judgment feedback."""

    def __init__(self, num_clients: int, seed: int = 0):
        self.num_clients = num_clients
        self._rng = np.random.default_rng(seed)

    @classmethod
    def from_config(cls, config, local):
        # seed + 1 keeps the draw stream identical to the legacy trainer's
        # use_pools=False path (its pool RNG held `seed`).
        return cls(config.num_clients, config.seed + 1)

    def select(self, num: int) -> list[int]:
        num = min(num, self.num_clients)
        return [int(i) for i in
                self._rng.choice(self.num_clients, num, replace=False)]

    def update(self, positives: Sequence[int],
               negatives: Sequence[int]) -> None:
        pass

    def stats(self) -> dict:
        # no pool bookkeeping exists; don't fabricate positive/negative
        # counts that could be mistaken for judgment outcomes
        return {"selector": "uniform", "num_clients": self.num_clients}


@register("selector", "catgroups")
class CatGrouper:
    """FedCAT device grouping over an inner selector (default uniform).

    ``select`` delegates to ``inner`` (so the draw stream — and therefore
    fixed-seed histories — matches the wrapped selector exactly), then
    packs the selection into ordered groups of ``group_size`` whose pooled
    label distributions are greedily entropy-maximized. The server binds
    the client corpus at construction (:meth:`bind_data`), which is where
    the per-device label histograms come from; an unbound grouper falls
    back to chaining devices in selection order.

    ``last_groups`` holds the current round's groups as lists of *relative*
    indices into the selection — the contract ``CatChainStrategy`` and
    ``DeviceConcatAggregator`` consume. Grouping is deterministic in the
    selection, so a speculative re-selection on a selector copy reproduces
    identical chains.
    """

    inner_cls = UniformSelector

    def __init__(self, inner, group_size: int = 2):
        self.inner = inner
        self.group_size = max(1, int(group_size))
        self._hists: np.ndarray | None = None
        self.last_groups: list[list[int]] | None = None

    @classmethod
    def from_config(cls, config, local):
        return cls(cls.inner_cls.from_config(config, local),
                   config.group_size)

    def bind_data(self, client_data) -> None:
        """Record per-device label histograms (corpus-cached when bound
        to a corpus of either plane, recomputed for a raw dict)."""
        self._hists = _corpus_histograms(client_data)

    def select(self, num: int) -> list[int]:
        sel = self.inner.select(num)
        if self._hists is not None:
            hists = self._hists[np.asarray(sel)]
        else:
            # unbound: degenerate one-class histograms -> groups chain the
            # selection in index order (still a valid partition)
            hists = np.ones((len(sel), 1))
        self.last_groups = greedy_entropy_groups(hists, self.group_size)
        return sel

    def update(self, positives: Sequence[int],
               negatives: Sequence[int]) -> None:
        self.inner.update(positives, negatives)

    def stats(self) -> dict:
        s = dict(self.inner.stats())
        s["group_size"] = self.group_size
        if self.last_groups is not None:
            s["num_groups"] = len(self.last_groups)
        return s


@register("selector", "catgroups-pools")
class PoolCatGrouper(CatGrouper):
    """CatGrouper over the paper's epsilon-greedy pools: judgment feedback
    re-files chain members, the synergy half of ``fedcat+maxent``."""

    inner_cls = PoolSelector


@register("selector", "queue")
class QueueSelector:
    """Entropy-driven participation with dynamic data queues
    (arXiv 2410.17792, heterogeneity cases per arXiv 2201.12515).

    Ranking: with probability ``eps`` the round exploits — the ``num``
    clients with the highest label-distribution entropy (read once off the
    bound corpus's cached histograms), fairness-damped by a per-selection
    ``fairness`` penalty so high-entropy clients don't monopolize rounds;
    otherwise it explores uniformly. Ties break to the lowest client id,
    so selection is a pure function of (rng stream, visit counts) and a
    speculative deepcopy replays it exactly.

    Queueing: every ``select`` advances a :class:`DataQueue` schedule and
    records each chosen client's released sample count;
    :meth:`data_schedule` hands those counts to the server, which masks
    them into the cohort's weight row inside the jitted corpus gather —
    the effective local dataset grows over training at zero transfer cost.

    Unbound (no corpus stats), selection degrades to uniform and the
    queue stays off — the selector never fabricates entropy ranks.
    """

    def __init__(self, num_clients: int, eps: float = 0.8, seed: int = 0,
                 queue: DataQueue | None = None, fairness: float = 0.05):
        self.num_clients = num_clients
        self.eps = eps
        self.fairness = fairness
        self.queue = queue or DataQueue()
        self._rng = np.random.default_rng(seed)
        self._uses = np.zeros(num_clients, np.int64)
        self._entropy: np.ndarray | None = None
        self._sizes: np.ndarray | None = None
        self._last_active: np.ndarray | None = None
        self._last_frac: float | None = None   # schedule last applied
        self.round_idx = 0
        self._pos = 0
        self._neg = 0

    @classmethod
    def from_config(cls, config, local):
        return cls(config.num_clients, config.eps, config.seed)

    def bind_data(self, client_data) -> None:
        """Pull per-client entropy ranks + real sizes off the corpus
        (either plane — the stats surface is duck-typed)."""
        if hasattr(client_data, "label_entropy"):
            self._entropy = client_data.label_entropy()
            self._sizes = client_data.sizes()
        else:
            hists = _corpus_histograms(client_data)
            self._entropy = np.asarray(
                [hist_entropy(h) for h in hists], np.float64)
            w = np.asarray(client_data["w"]) if "w" in client_data else None
            self._sizes = (np.full(len(hists), np.asarray(
                client_data["y"]).shape[1], np.int64) if w is None
                else w.sum(axis=1).astype(np.int64))

    def select(self, num: int) -> list[int]:
        num = min(num, self.num_clients)
        if self._entropy is not None and self._rng.random() < self.eps:
            score = self._entropy - self.fairness * self._uses
            order = np.lexsort((np.arange(self.num_clients), -score))
            sel = order[:num]
        else:
            sel = self._rng.choice(self.num_clients, num, replace=False)
        sel = [int(i) for i in sel]
        self._uses[sel] += 1
        if self._sizes is None:
            self._last_active = None
        else:
            self._last_active = self.queue.active(self.round_idx,
                                                  self._sizes[sel])
            self._last_frac = self.queue.frac(self.round_idx)
        self.round_idx += 1
        return sel

    def data_schedule(self, sel) -> np.ndarray | None:
        """Released-sample counts for the selection :meth:`select` just
        produced (the contract ``Server._run_cohort`` consumes); None
        until a corpus is bound."""
        return self._last_active

    def update(self, positives: Sequence[int],
               negatives: Sequence[int]) -> None:
        self._pos += len(positives)
        self._neg += len(negatives)

    def stats(self) -> dict:
        # queue_frac is the schedule the LAST select actually applied —
        # None before any select (or while unbound, when the queue is
        # off), never a peek at the upcoming round's frac (the old
        # `frac(round_idx - 1)` reported round 0's frac at construction
        # as if a round had run)
        return {"selector": "queue", "round": self.round_idx,
                "queue_frac": self._last_frac,
                "positive_total": self._pos, "negative_total": self._neg}
