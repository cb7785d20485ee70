"""The ``Server`` round driver: paper Alg. 2 with every axis pluggable.

One ``round()`` = select -> vmapped ClientUpdate -> judge -> aggregate ->
state/pool feedback. The data plane (client updates, aggregation) is
traced JAX over a stacked client axis; the control plane (selection,
judgment, pool bookkeeping) is host-side numpy — exactly the split the
legacy ``FedEntropyTrainer`` used, so fixed-seed round histories are
bit-for-bit reproducible.

Client data lives on a *data plane* (``data_plane=`` keyword, resolved by
:func:`repro.data.stream.as_data_plane`): device-resident
:class:`repro.data.corpus.ClientCorpus` by default (a plain stacked dict
is wrapped on construction), or the host-resident streaming
:class:`repro.data.stream.HostCorpus` when N doesn't fit. Either way the
per-round cohort reaches the device via ``corpus.cohort(idx)`` — a jitted
on-device gather (resident) or a host gather + single-cohort upload
(streaming) — the corpus keeps its storage dtype (uint8 ingest normalizes
inside the traced finish), and selectors draw their control-plane stats
(label histograms, sizes) off the corpus instead of recomputing them.
Selectors exposing ``data_schedule(sel)`` (the
dynamic-data-queue selector) have their per-client release counts
applied as a weight mask inside the same gather.

Compiled programs live in a per-server bounded LRU cache
(``ServerConfig.jit_cache_size``), not a module-global dict: a benchmark
sweep that builds hundreds of servers no longer accumulates params-sized
XLA executables for the lifetime of the process.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.aggregation import comm_bytes
from ..core.strategies import ApplyFn, client_update, cross_entropy
from ..data.stream import as_data_plane, plane_of
from . import spans
from .protocols import Aggregator, ClientStrategy, Judge, Selector


@dataclass(frozen=True)
class ServerConfig:
    """Round-loop parameters (paper Sec. 4.1 defaults)."""
    num_clients: int = 100          # paper N
    participation: float = 0.1      # paper C
    eps: float = 0.8                # paper epsilon (eps-greedy selectors)
    seed: int = 0
    jit_cache_size: int = 4         # per-server compiled-program LRU bound
    group_size: int = 2             # FedCAT chain length (catgroups/catchain)
    num_clusters: int = 1           # K model-bank centers (1 = unclustered)

    def cohort_size(self) -> int:
        """|S_t| = max(1, round(N * C)) — the one place the paper's
        cohort sizing lives; every engine reads it here. Python's
        ``round`` is banker's (half-to-even): N=25, C=0.1 selects 2."""
        return max(1, int(round(self.num_clients * self.participation)))


class BoundedJitCache:
    """Tiny LRU for compiled programs, owned by one ``Server``.

    Thread-safe: the streaming data plane's cohort prefetcher runs on a
    background thread, so cache access is no longer guaranteed
    host-serial. ``make()`` runs *outside* the lock — a multi-second XLA
    compile must not stall other threads' lookups of unrelated keys —
    with per-key once semantics: concurrent callers of the same missing
    key dedupe onto one build (the others block on a per-key event and
    adopt the builder's entry).
    """

    def __init__(self, maxsize: int):
        self.maxsize = max(1, int(maxsize))
        self._entries: OrderedDict[Any, Any] = OrderedDict()
        self._building: dict[Any, threading.Event] = {}
        self._lock = threading.RLock()

    def _record(self, hit: bool) -> None:
        """Stats hook (called under the lock); subclasses count hits."""

    def get(self, key, make: Callable[[], Any]):
        while True:
            with self._lock:
                if key in self._entries:
                    self._entries.move_to_end(key)
                    self._record(True)
                    return self._entries[key]
                ev = self._building.get(key)
                if ev is None:
                    ev = self._building[key] = threading.Event()
                    break
            # another thread is compiling this key: wait, then re-probe
            # (if its build failed, or the entry was evicted before we
            # re-probed, we become the builder on the next pass)
            ev.wait()
        try:
            fn = make()
        except BaseException:
            with self._lock:
                self._building.pop(key, None)
            ev.set()
            raise
        with self._lock:
            self._entries[key] = fn
            self._record(False)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
            self._building.pop(key, None)
        ev.set()
        return fn

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


def _make_client_fn(apply_fn: ApplyFn, spec, in_axes):
    """vmapped ClientUpdate with the strategy's state slices as extra args."""

    def one(global_params, data, prev_p, c_loc, c_glob):
        return client_update(
            apply_fn, global_params, data, spec,
            prev_params=prev_p, c_local=c_loc, c_global=c_glob)

    # jitted, the program is ``jit_client_update`` on a profiler trace
    one.__name__ = one.__qualname__ = "client_update"
    return jax.vmap(one, in_axes=in_axes)


class Server:
    """Host-side FL driver; compose with :func:`repro.fl.build` or directly::

        server = Server(apply_fn, params, data, ServerConfig(num_clients=32),
                        selector=PoolSelector(32), strategy=FedAvgStrategy(),
                        judge=MaxEntropyJudge(),
                        aggregator=WeightedAverageAggregator())
        server.fit(rounds=60, eval_every=5, eval_data=(xte, yte))
    """

    def __init__(
        self,
        apply_fn: ApplyFn,
        init_params,
        client_data: dict,          # x:(N,S,...), y:(N,S), w:(N,S)
        config: ServerConfig,
        *,
        selector: Selector,
        strategy: ClientStrategy,
        judge: Judge,
        aggregator: Aggregator,
        data_plane: str = "auto",
        cluster=None,
        drift=None,
    ):
        self.apply_fn = apply_fn
        self.global_params = init_params
        # the data plane: device-resident (fast path) or host-resident
        # streaming, per `data_plane` — an already-constructed corpus of
        # either plane passes through under "auto". Both planes are
        # Mappings, so `self.data` keeps its seed-era dict-like surface.
        self.corpus = as_data_plane(client_data, data_plane)
        self.data = self.corpus
        self.config = config
        self.selector = selector
        self.strategy = strategy
        self.judge = judge
        self.aggregator = aggregator
        self.state = strategy.init_state(init_params, config.num_clients)
        self.round_idx = 0
        self.history: list[dict] = []
        self._jit_cache = BoundedJitCache(config.jit_cache_size)
        # selectors that stat the corpus (CatGrouper's label histograms,
        # the queue selector's entropy ranking) bind it once here — the
        # corpus owns the cached control-plane stats
        bind = getattr(selector, "bind_data", None)
        if bind is not None:
            bind(self.corpus)
        # ---- the optional cluster axis (K-center ModelBank) ----------
        # K=1 (or no assigner) keeps bank=None: every code path below is
        # byte-identical to the single-model server, which is what makes
        # clustered compositions reduce to the seed goldens exactly.
        self.cluster = cluster
        k = (getattr(cluster, "num_clusters", 1)
             if cluster is not None else 1)
        if k > 1:
            if getattr(strategy, "make_client_fn", None) is not None or \
                    getattr(strategy, "prepare_round", None) is not None:
                raise ValueError(
                    f"{type(strategy).__name__} builds its own client "
                    "fan-out (chains/groups); the clustered ModelBank "
                    "needs the plain vmapped ClientUpdate to thread "
                    "per-client start params")
            if self.state is not None:
                raise ValueError(
                    f"{type(strategy).__name__} carries cross-round "
                    "client state; clustered rounds support stateless "
                    "strategies only (per-cluster control variates are a "
                    "recorded ROADMAP follow-up)")
            from .clusters import ModelBank
            self.bank = ModelBank.init(init_params, k, seed=config.seed)
            self.global_params = self.bank.stacked
        else:
            self.bank = None
        if cluster is not None:
            bindc = getattr(cluster, "bind", None)
            if bindc is not None:
                bindc(self)
        # ---- the optional drift schedule -----------------------------
        # events apply at the START of their round (before selection),
        # replacing the drifting clients' stacked rows and rebinding the
        # data plane + selector stats; see repro.data.partition.
        self._drift = sorted(list(drift or ()), key=lambda e: e.round)
        s = self.corpus.samples_per_client
        for ev in self._drift:
            got = {kk: np.shape(v)[1] for kk, v in ev.data.items()}
            if any(v != s for v in got.values()):
                raise ValueError(
                    f"drift event at round {ev.round} carries rows of "
                    f"sample length {got}, corpus has {s} "
                    "(regenerate with samples_per_client=corpus's)")

    # ------------------------------------------------------------------
    def _compile_cache(self):
        """Per-server LRU by default; the process-level cache when the
        runtime subsystem's opt-in is enabled (keys below carry the
        apply_fn identity so sharing across servers is sound)."""
        from .runtime.compile_cache import process_cache
        cache = process_cache()
        # explicit None check: an empty cache is len()==0, hence falsy
        return self._jit_cache if cache is None else cache

    def _client_key(self) -> tuple:
        # the apply_fn itself (identity hash) keys the entry — embedding
        # the object rather than id() pins it for the cache's lifetime,
        # so a GC'd callable can never alias a reused address. Strategies
        # that build their own client fn (chains) key on their class so a
        # vmapped program can never serve a chain cohort or vice versa.
        tag = ("client" if getattr(self.strategy, "make_client_fn", None)
               is None else f"client-{type(self.strategy).__name__}")
        return (tag, self.apply_fn, self.strategy.spec,
                self._client_in_axes(), self.corpus.signature())

    def _client_in_axes(self) -> tuple:
        """The strategy's vmap in_axes — with the params slot mapped
        (axis 0) on clustered servers: each cohort row then trains from
        its own bank center (``ModelBank.gather``'s (m, ...) stack)
        instead of one broadcast global model. Part of the compile-cache
        key, so banked and broadcast programs never alias."""
        ax = tuple(self.strategy.client_in_axes())
        return ((0,) + ax[1:]) if self.bank is not None else ax

    def _client_fn(self):
        make = getattr(self.strategy, "make_client_fn", None)
        if make is not None:
            return self._compile_cache().get(
                self._client_key(), lambda: jax.jit(make(self.apply_fn)))
        return self._compile_cache().get(
            self._client_key(), lambda: jax.jit(_make_client_fn(
                self.apply_fn, self.strategy.spec,
                self._client_in_axes())))

    def _eval_fn(self):
        fn = self.apply_fn
        return self._compile_cache().get(
            ("eval", fn), lambda: jax.jit(lambda p, bx: fn(p, bx)[0]))

    # ------------------------------------------------------------------
    def _run_cohort(self, sel, selector, global_params=None):
        """Gather, lay out, and launch the cohort's client compute (async).

        The cohort comes off the data plane — a jitted on-device gather
        along the resident corpus's client axis (only ``idx`` and a
        data-queue schedule, if the selector has one, cross the
        host→device boundary), or a host gather + cohort-sized upload on
        the streaming plane (which may consume a prefetched staging).
        Group-aware strategies
        (``prepare_round``) re-lay the gathered cohort into chain groups
        read off ``selector`` — the selector that produced ``sel``, which
        under speculation may be a throwaway copy: the group, not the
        device, is the dispatch unit, and its structure is captured at
        dispatch time.
        """
        gp = self.global_params if global_params is None else global_params
        with TraceAnnotation(spans.STAGE):
            idx = np.asarray(sel)
            sched = getattr(selector, "data_schedule", None)
            active = None if sched is None else sched(sel)
            data = self.corpus.cohort(idx, active=active)
            prev_p, c_loc, c_glob = self.strategy.client_inputs(self.state,
                                                                idx)
            prep = getattr(self.strategy, "prepare_round", None)
            if prep is not None:
                gdata, aux = prep(data, selector)
        with TraceAnnotation(spans.CLIENTS):
            if prep is None:
                return self._client_fn()(gp, data, prev_p, c_loc, c_glob)
            out = self._client_fn()(gp, gdata, prev_p, c_loc, c_glob,
                                    aux["valid"])
            return self.strategy.finish_round(out, aux)

    # -------------------------------------------------------------- drift
    def _apply_drift(self) -> list:
        """Apply every drift event scheduled for the CURRENT round (before
        selection): replace the drifting clients' stacked rows, rebuild
        the corpus on its own plane, and rebind selector stats. Returns
        the applied events (history annotates drift rounds)."""
        applied = []
        while self._drift and self._drift[0].round == self.round_idx:
            ev = self._drift.pop(0)
            # as_numpy() may hand back read-only device views / memory
            # maps: copy only the arrays the event actually rewrites
            arrays = self.corpus.as_numpy()
            ids = np.asarray(ev.clients, np.int64)
            for key, rows in ev.data.items():
                if key in arrays:
                    arrays[key] = np.array(arrays[key])
                    arrays[key][ids] = np.asarray(
                        rows, arrays[key].dtype)
            transform = getattr(self.corpus, "transform", None)
            self.corpus = as_data_plane(arrays, plane_of(self.corpus),
                                        transform=transform)
            self.data = self.corpus
            bind = getattr(self.selector, "bind_data", None)
            if bind is not None:
                bind(self.corpus)
            applied.append(ev)
        return applied

    def _drift_at(self, round_no: int) -> bool:
        """True if a drift event is still scheduled for ``round_no`` —
        the pipelined engine must not speculate across that boundary."""
        return any(ev.round == round_no for ev in self._drift)

    def _launch(self, sel, selector, cluster_ids=None, start=None,
                bank=None):
        """Start a cohort's client compute: from ``start`` (default the
        global model), or, given ``cluster_ids``, from each client's
        center gathered off ``bank`` (the server's own unless a
        speculative bank is passed)."""
        if cluster_ids is not None:
            bank = self.bank if bank is None else bank
            with TraceAnnotation(spans.STAGE):
                start = bank.gather(cluster_ids)
        return self._run_cohort(sel, selector, start)

    # ------------------------------------------------------ the round tail
    # Paper Alg. 2 after the client programs: readback -> verdict ->
    # aggregate -> feedback -> record. Every engine runs these stages, so
    # every engine opens the same ``fl.*`` spans and keeps the same record.
    @staticmethod
    def _readback(out):
        """The round's two readbacks: soft labels and sizes, float64."""
        return (spans.fetch(out["soft_label"], np.float64),
                spans.fetch(out["size"], np.float64))

    def _verdict(self, soft, sizes, sel, cluster_ids=None):
        """The float64 verdict of record over the cohort ``sel``.

        Returns ``(mask, pos, neg, entropy, clusters)``: the 0/1 admission
        mask, positive and negative client ids (the judge's own order),
        the entropy and, on clustered rounds, the per-cluster verdicts
        the history records (``None`` otherwise). Unclustered, it is one
        judge call and its entropy. Clustered, the judge runs on each
        cluster's member rows (clusters ascending), and the entropy is
        the member-count-weighted mean of the clusters' entropies.
        """
        with TraceAnnotation(spans.JUDGE):
            mask = np.zeros(len(sel), np.float32)
            if cluster_ids is None:
                a_rel, r_rel, ent = self.judge(soft, sizes)
                mask[a_rel] = 1.0
                return (mask, [sel[i] for i in a_rel],
                        [sel[i] for i in r_rel], ent, None)
            cluster_ids = np.asarray(cluster_ids)
            pos, neg, clusters, ents = [], [], {}, []
            for k in sorted(int(c) for c in np.unique(cluster_ids)):
                rows = np.where(cluster_ids == k)[0]
                a_rel, r_rel, ent = self.judge(soft[rows], sizes[rows])
                mask[rows[a_rel]] = 1.0
                p = [sel[int(rows[i])] for i in a_rel]
                n = [sel[int(rows[i])] for i in r_rel]
                pos.extend(p)
                neg.extend(n)
                clusters[str(k)] = {
                    "members": [sel[int(i)] for i in rows],
                    "positive": p, "negative": n, "entropy": ent}
                if not np.isnan(ent):
                    ents.append((len(rows), ent))
            total = sum(n for n, _ in ents)
            entropy = (sum(n * e for n, e in ents) / total
                       if total else float("nan"))
            return mask, pos, neg, entropy, clusters

    def _aggregate(self, start, out, weights, mask, cluster_ids=None):
        """The aggregator over the cohort from ``start`` (the global model
        or the K-stacked bank), with float32 weights and the mask."""
        if cluster_ids is not None:
            out = dict(out)
            out["cluster"] = jnp.asarray(np.asarray(cluster_ids), jnp.int32)
        return self.aggregator(start, out, jnp.asarray(weights, jnp.float32),
                               jnp.asarray(mask))

    def _fold(self, start, out, sel, cluster_ids=None) -> None:
        """The verdict-independent feedback: strategy state, and the
        clustered assignment state, both against the pre-aggregation
        model ``start`` / bank."""
        self.state = self.strategy.update_state(
            self.state, start, out, np.asarray(sel), self.config.num_clients)
        if cluster_ids is not None:
            self.cluster.update(sel, cluster_ids, out, self.bank)

    def _merge(self, start, out, weights, verdict, sel, cluster_ids=None, *,
               new=None, folded=False, fed=False, **extra) -> dict:
        """Aggregate and feed back one judged cohort; returns its record.

        ``new`` is an aggregate already computed (a confirmed
        speculation), ``folded`` says :meth:`_fold` already ran, and
        ``fed`` that the selector already saw the verdict. ``extra``
        keys follow the record's own.
        """
        with TraceAnnotation(spans.AGGREGATE):
            if new is None:
                new = self._aggregate(start, out, weights, verdict[0],
                                      cluster_ids)
        with TraceAnnotation(spans.FEEDBACK):
            if not folded:
                self._fold(start, out, sel, cluster_ids)
            if self.bank is None:
                self.global_params = new
            else:
                self.bank = self.bank.replace(new)
                self.global_params = self.bank.stacked
            return self._record(sel, verdict, out["soft_label"].shape[-1],
                                cluster_ids, fed=fed, **extra)

    def _record(self, sel, verdict, num_classes, cluster_ids=None, *,
                fed=False, **extra) -> dict:
        """Selector feedback, uplink bytes and the round's record."""
        _, pos, neg, ent, clusters = verdict
        if not fed:
            self.selector.update(pos, neg)
        # uplink accounting per the paper's model: positives ship ONE
        # model each (their own center on a clustered server, so the
        # template is a single center, never the K-stacked bank)
        template = (self.global_params if self.bank is None
                    else self.bank.center(0))
        comm = comm_bytes(template, len(sel), len(pos), num_classes,
                          control_variate=self.strategy.doubles_uplink)
        rec = {"round": self.round_idx, "selected": sel, "positive": pos,
               "negative": neg, "entropy": ent, "comm": comm}
        if clusters is not None:
            rec["cluster"] = [int(c) for c in cluster_ids]
            rec["clusters"] = clusters
        rec.update(extra)
        return rec

    def _commit(self, rec: dict, drifted=()) -> dict:
        """Append the record and advance. A clustered record notes the
        drift events applied before its round (``drift``); an unclustered
        record keeps the paper's keys."""
        if drifted and self.bank is not None:
            rec["drift"] = [list(ev.clients) for ev in drifted]
        self.history.append(rec)
        self.round_idx += 1
        return rec

    def _play(self, sel, cluster_ids=None, start=None, **extra) -> dict:
        """The sequential round after selection: launch the cohort from
        ``start`` (default the global model), read back, judge, merge."""
        start = self.global_params if start is None else start
        out = self._launch(sel, self.selector, cluster_ids, start)
        soft, sizes = self._readback(out)
        verdict = self._verdict(soft, sizes, sel, cluster_ids)
        return self._merge(start, out, sizes, verdict, sel, cluster_ids,
                           **extra)

    def round(self) -> dict:
        """One paper Alg. 2 round; returns the history record. Its steps
        run under the profiler spans of :mod:`repro.fl.spans`."""
        with TraceAnnotation(spans.ROUND, round=self.round_idx):
            drifted = self._apply_drift()
            with TraceAnnotation(spans.SELECT):
                sel = self.selector.select(self.config.cohort_size())
                cids = (None if self.bank is None
                        else self.cluster.assign(sel))
            return self._commit(self._play(sel, cids), drifted)

    # ------------------------------------------------------------------
    def evaluate(self, x: jax.Array, y: jax.Array,
                 batch: int = 512, center: int | None = None) -> dict:
        """Test-set accuracy/loss. On a clustered server ``center`` picks
        the bank center to score (default 0 — the un-jittered lineage of
        the init params); unclustered servers ignore it."""
        n = x.shape[0]
        if n == 0:
            # loud, immediate: batch=min(batch,0)=0 would otherwise die in
            # range(0, 0, 0) before the correct/n ZeroDivisionError could
            raise ValueError("empty eval set (x has 0 rows)")
        params = self.global_params if self.bank is None \
            else self.bank.center(0 if center is None else int(center))
        batch = min(batch, n)
        correct, loss_sum = 0.0, 0.0
        f = self._eval_fn()
        for i in range(0, n, batch):
            bx, by = x[i:i + batch], y[i:i + batch]
            m = bx.shape[0]
            if m < batch:
                # edge-pad the tail batch to the full shape so every batch
                # runs the one compiled program (no n % batch variants);
                # padded rows are sliced off the logits before scoring
                reps = jnp.broadcast_to(bx[-1:], (batch - m,) + bx.shape[1:])
                bx = jnp.concatenate([bx, reps], axis=0)
            logits = f(params, bx)[:m]
            correct += float(jnp.sum(jnp.argmax(logits, -1) == by))
            loss_sum += float(cross_entropy(logits, by)) * m
        return {"accuracy": correct / n, "loss": loss_sum / n}

    def fit(self, rounds: int, eval_every: int = 0, eval_data=None) -> list:
        """Run ``rounds`` rounds; returns periodic eval metrics (if any)."""
        evals = []
        for r in range(rounds):
            self.round()
            if eval_every and eval_data is not None and \
                    (r + 1) % eval_every == 0:
                m = self.evaluate(*eval_data)
                m["round"] = self.round_idx
                evals.append(m)
        return evals


def total_uplink_bytes(history: list[dict]) -> int:
    return int(sum(h["comm"]["total_bytes"] for h in history))
