"""The pipelined, mesh-sharded round engine.

``PipelinedServer`` runs the exact Selector/ClientStrategy/Judge/Aggregator
composition of :class:`repro.fl.Server` with two independent levers:

**Sharding** (``RuntimeConfig.shard``): the stacked client axis of the
vmapped ClientUpdate is partitioned over a 1-D ``("clients",)`` device
mesh with ``shard_map`` (see :mod:`.sharding`), so |S_t| clients train on
``len(devices)`` chips instead of one. The ``ClientCorpus`` is laid out
over the same mesh exactly once (``corpus.shard``), so both the initial
dispatch and the speculative re-dispatch path gather their cohorts on
device from the resident corpus — the per-dispatch host slice + H2D
copy is gone. ``"auto"`` (default) shards only when more than one device
exists — on a single host device the engine compiles the identical
program a sequential ``Server`` would, which is what makes the
golden-history equivalence bit-for-bit.

**Speculation** (``RuntimeConfig.speculate``): paper Alg. 2 serializes
device compute behind the host-side float64 judgment oracle. The engine
breaks that chain by *speculating the verdict on device*: the traced
float32 judge (``core.judgment.judge``, ``spec_backend="xla"`` or
``"pallas"`` for the class-tiled kernel) produces a mask without leaving
the accelerator, aggregation and the next round's cohort compute dispatch
against it immediately (JAX async dispatch), and only then does the host
run the float64 oracle on the already-transferred soft labels. The two
judges provably agree except at float32 tie margins (tests/test_judgment),
so almost every round the oracle merely confirms the in-flight round t+1.
On a mismatch the speculated buffers are discarded and round t+1
re-dispatches from the oracle verdict — history records ``spec_hit`` per
round and ``redispatched`` on rounds whose compute was re-issued.

On the *streaming* data plane (:class:`repro.data.stream.HostCorpus`)
the same speculated selection doubles as the **prefetch target**: rather
than dispatching round t+1 eagerly (its host gather + H2D upload would
block the round loop), the engine hands the predicted cohort to the
corpus's background :class:`~repro.data.stream.CohortPrefetcher` and
dispatches only after the oracle confirms — the upload overlaps the
oracle's own device sync, and a misprediction cancels the staged buffers
with no wasted compute. Histories stay bit-for-bit: the dispatch runs
the identical programs on the identical inputs either side of the
oracle.

History and parameters are bit-for-bit identical to the sequential
``Server`` in BOTH modes: recorded verdicts/entropy always come from the
float64 oracle, the selector's RNG stream advances exactly as it would
sequentially (speculative draws happen on a throwaway deepcopy that is
adopted only when the verdict matches), and a confirmed speculative
aggregation is numerically the same float32 reduction the sequential path
runs.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ...core.judgment import judge as traced_judge
from .. import spans
from ..judges import MaxEntropyJudge
from ..registry import register
from ..server import Server
from .sharding import (
    CLIENT_AXIS, client_mesh_from, make_client_mesh, make_sharded_client_fn,
)


@dataclass(frozen=True)
class RuntimeConfig:
    """Engine knobs; the defaults reproduce sequential ``Server`` behavior
    on one device and turn on mesh sharding automatically on many."""
    speculate: bool = False        # overlap oracle judgment with round t+1
    shard: object = "auto"         # True | False | "auto" (shard iff >1 dev)
    spec_backend: str = "xla"      # device judge for speculation: xla|pallas
    donate_data: bool = True       # donate per-round cohort data buffers


@register("engine", "sequential")
class SequentialEngine(Server):
    """Alias of :class:`repro.fl.Server` under the engine registry; accepts
    (and ignores) ``runtime=`` so ``build(..., engine=...)`` is uniform."""

    runtime_cls = RuntimeConfig   # build() rejects mismatched configs

    def __init__(self, *args, runtime: RuntimeConfig | None = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self.runtime = runtime or RuntimeConfig()


@register("engine", "pipelined")
class PipelinedServer(Server):
    """Pipelined/sharded drop-in for ``Server`` (same composition axes)."""

    runtime_cls = RuntimeConfig   # build() rejects mismatched configs

    def __init__(self, *args, runtime: RuntimeConfig | None = None,
                 mesh=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.runtime = runtime or RuntimeConfig()
        if not isinstance(self.runtime, RuntimeConfig):
            # loud on direct construction too (build() catches it earlier):
            # an AsyncConfig here would half-work until .speculate access
            raise ValueError(
                f"{type(self).__name__} takes runtime=RuntimeConfig, got "
                f"{type(self.runtime).__name__}")
        self._mesh = mesh
        self._pending = None           # (sel, cids, out) for round t+1
        self._redispatch_next = False  # previous speculation missed

    # ---------------------------------------------------------- sharding
    def _shard_enabled(self) -> bool:
        if self.runtime.shard == "auto":
            return len(jax.devices()) > 1
        return bool(self.runtime.shard)

    def client_mesh(self):
        """The 1-D ("clients",) mesh sharded rounds run on. A production
        ("pod", "data", "model") mesh passed at construction is reduced to
        its client rows (see :func:`.sharding.client_mesh_from`)."""
        if self._mesh is None:
            self._mesh = make_client_mesh()
        elif CLIENT_AXIS not in self._mesh.shape:
            self._mesh = client_mesh_from(self._mesh)
        return self._mesh

    def _client_fn(self):
        if not self._shard_enabled():
            return super()._client_fn()
        mesh = self.client_mesh()
        # the corpus is laid out over the client mesh exactly once
        # (idempotent): cohort gathers then run as SPMD programs over the
        # sharded operand and land distributed for the shard_map fan-out —
        # no per-dispatch host→device copy, no per-round resharding.
        # Uneven N pads to the next mesh multiple (P("clients") always,
        # never replicated), and the speculative re-dispatch path gathers
        # from the same padded-sharded operand. Must run before
        # _client_key(): the signature keys on the padded layout.
        self.corpus.shard(mesh)
        key = ("sharded",) + self._client_key() + (
            mesh.shape[CLIENT_AXIS], self.runtime.donate_data)
        make = getattr(self.strategy, "make_client_fn", None)
        return self._compile_cache().get(
            key, lambda: make_sharded_client_fn(
                self.apply_fn, self.strategy.spec,
                self._client_in_axes(), mesh,
                donate_data=self.runtime.donate_data,
                # chain strategies shard whole groups, not devices: the
                # inner fn's leading axis is the group axis and takes the
                # extra axis-0 validity mask; group-free custom clients
                # (lmstep) keep the plain five-argument signature
                inner=None if make is None else make(self.apply_fn),
                inner_axes=(0,) if getattr(
                    self.strategy, "prepare_round", None) is not None
                else ()))

    # -------------------------------------------------------- speculation
    def _traced_judge_fn(self):
        """Jitted on-device verdict for speculation; None disables it."""
        def make():
            # exact class (not subclasses, which may override traced()):
            # the runtime's spec_backend picks the device implementation
            if type(self.judge) is MaxEntropyJudge:
                backend = self.runtime.spec_backend

                def fn(s, z):
                    return traced_judge(s, z, backend=backend)
            else:
                traced = getattr(self.judge, "traced", None)
                if traced is None:
                    return None
                fn = traced()
            return jax.jit(fn)
        return self._compile_cache().get(
            ("spec-judge", self.judge, self.runtime.spec_backend), make)

    # ------------------------------------------------------------- rounds
    def round(self) -> dict:
        if not self.runtime.speculate:
            return super().round()
        spec_fn = self._traced_judge_fn()
        if spec_fn is None:       # judge has no traced form: stay sequential
            return super().round()
        with TraceAnnotation(spans.ROUND, round=self.round_idx):
            return self._speculative_round(spec_fn)

    def _speculate(self, spec_fn, out, cluster_ids):
        """The device verdict on a dispatched cohort, left on the device:
        one traced judge call, or one per cluster (clusters ascending, the
        oracle's order) combined into one cohort mask.

        Returns ``(groups, mask)``: each judged ``(rows, JudgmentResult)``
        and the 0/1 cohort mask the speculative aggregate runs with.
        """
        sizes32 = out["size"].astype(jnp.float32)
        soft32 = out["soft_label"].astype(jnp.float32)
        if cluster_ids is None:
            jr = spec_fn(soft32, sizes32)
            return [(np.arange(soft32.shape[0]), jr)], jr.mask
        cids = np.asarray(cluster_ids)
        groups, mask = [], jnp.zeros(len(cids), jnp.float32)
        for k in sorted(int(c) for c in np.unique(cids)):
            rows = np.where(cids == k)[0]
            jr = spec_fn(jnp.take(soft32, rows, axis=0),
                         jnp.take(sizes32, rows, axis=0))
            groups.append((rows, jr))
            mask = mask.at[rows].set(jr.mask)
        return groups, mask

    @staticmethod
    def _read_speculation(groups, sel):
        """Read the device verdict back: its host mask, and the positive
        and negative ids the throwaway selector is filed with."""
        mask = np.zeros(len(sel), np.float32)
        pos, neg = [], []
        for rows, jr in groups:
            mk = spans.fetch(jr.mask)
            mask[rows[mk > 0]] = 1.0
            pos.extend(sel[int(rows[i])] for i in range(len(rows))
                       if mk[i] > 0)
            if jr.removal_order is not None:
                order = spans.fetch(jr.removal_order)
                neg.extend(sel[int(rows[int(r)])] for r in order if r >= 0)
            else:
                # order-less judges (e.g. budgeted): index order — pools
                # are set-based, so only the SET must match the oracle
                # verdict
                neg.extend(sel[int(rows[i])] for i in range(len(rows))
                           if mk[i] == 0)
        return mask, pos, neg

    def _speculative_round(self, spec_fn) -> dict:
        """One round with the next round's cohort speculated on device.

        The device verdict and aggregate of this round's cohort are
        computed first; round t+1 is drawn on a throwaway selector copy
        filed with that verdict and dispatched from that aggregate (on a
        clustered server: assigned against the speculated bank — on an
        oracle hit bitwise the one the sequential path produces). The
        float64 oracle then judges this round; a hit adopts the speculated
        aggregate and selector, a miss discards them and merges from the
        oracle verdict exactly as ``Server.round``.
        """
        # drift applies BEFORE selection, exactly as sequentially. The
        # spec_next gate below guarantees no pending dispatch ever spans a
        # drift boundary, so the corpus swap never invalidates in-flight
        # compute (and never desyncs the adopted selector stream).
        drifted = self._apply_drift()
        num = self.config.cohort_size()
        if self._pending is not None:
            sel, cids, out = self._pending
            self._pending = None
            redispatched = False
        else:
            with TraceAnnotation(spans.SELECT):
                sel = self.selector.select(num)
                cids = (None if self.bank is None
                        else self.cluster.assign(sel))
            out = self._launch(sel, self.selector, cids)
            redispatched = self._redispatch_next
        self._redispatch_next = False
        # round t+1 re-partitions some clients' data: dispatching it now
        # would train on the PRE-drift corpus. Keep round t's verdict
        # speculation (the aggregation overlap is still real) but skip the
        # next-round dispatch — t+1 selects synchronously after the swap.
        spec_next = not self._drift_at(self.round_idx + 1)
        start = self.global_params

        # --- device-side speculative verdict and aggregate (all async),
        # then the verdict read back -------------------------------------
        with TraceAnnotation(spans.JUDGE):
            groups, spec_dev = self._speculate(spec_fn, out, cids)
        with TraceAnnotation(spans.AGGREGATE):
            new_spec = self._aggregate(start, out, out["size"], spec_dev,
                                       cids)
        with TraceAnnotation(spans.FEEDBACK):
            # verdict-independent (Alg. 2), so valid either way: fold it
            # before the speculative dispatch, which slices its client
            # inputs from the state and (clustered) assigns from the
            # sticky assignment state
            self._fold(start, out, sel, cids)
        bank_spec = None if self.bank is None else self.bank.replace(
            new_spec)
        with TraceAnnotation(spans.JUDGE):
            spec_mask, spec_pos, spec_neg = self._read_speculation(groups,
                                                                   sel)

        # --- speculatively select + dispatch round t+1 on a throwaway copy
        prefetch = getattr(self.corpus, "prefetch", None)
        # clustered dispatch is always eager (no prefetch deferral): the
        # assignment itself must evaluate the cohort's data, so the gather
        # cannot be deferred behind the oracle anyway
        deferred = spec_next and prefetch is not None and cids is None
        next_out = None
        if spec_next:
            with TraceAnnotation(spans.SELECT):
                sel_copy = copy.deepcopy(self.selector)
                sel_copy.update(spec_pos, spec_neg)
                next_sel = sel_copy.select(num)
                next_cids = (None if cids is None else
                             self.cluster.assign(next_sel, bank=bank_spec))
            # group assignment rides with the dispatch: sel_copy made (and,
            # for chain strategies, grouped) this selection, so it is the
            # selector the cohort layout is read from
            if deferred:
                # streaming plane: a dispatch here would block THIS thread
                # on the host gather + H2D upload of round t+1's cohort.
                # Stage it on the prefetch thread instead, so the upload
                # overlaps the oracle's block on round t's soft labels
                # below; the dispatch itself waits for the verdict (on a
                # miss only the staged buffers are thrown away). The
                # schedule read is idempotent, so the dispatch's own read
                # sees bit-identical counts.
                sched = getattr(sel_copy, "data_schedule", None)
                prefetch(np.asarray(next_sel),
                         None if sched is None else sched(next_sel))
            else:
                next_out = self._launch(next_sel, sel_copy, next_cids,
                                        new_spec, bank_spec)

        # --- float64 oracle on host, overlapping the in-flight compute ---
        soft, sizes = self._readback(out)
        verdict = self._verdict(soft, sizes, sel, cids)
        hit = bool(np.array_equal(verdict[0], spec_mask))
        if hit and spec_next:
            self.selector = sel_copy          # same verdict -> same stream
            if next_out is None:
                # streaming plane: consumes the staged buffers (a hit in
                # the prefetcher) instead of gathering synchronously
                next_out = self._launch(next_sel, sel_copy, None, new_spec)
            self._pending = (next_sel, next_cids, next_out)
        elif not hit:                          # discard, redo from oracle
            if deferred:
                # selector misprediction: drop the staged cohort — the
                # re-selected round t+1 falls back to a synchronous gather
                self.corpus.cancel_prefetch()
            # a miss only forces a re-dispatch when a speculative t+1 was
            # actually issued (at a drift boundary nothing was in flight)
            self._redispatch_next = spec_next
        rec = self._merge(start, out, sizes, verdict, sel, cids,
                          new=new_spec if hit else None, folded=True,
                          fed=hit and spec_next, spec_hit=hit,
                          redispatched=redispatched)
        return self._commit(rec, drifted)
