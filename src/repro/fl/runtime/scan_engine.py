"""One-program rounds: R speculative rounds folded into a single scan.

Every engine so far surfaces to host once per round — selector draw,
float64 judgment, history append — and with the data plane resident
(PR 4/5) and the cohort gather traced, that per-round host round-trip is
the remaining serial cost. ``ScanServer`` (registry ``engine="scan"``,
``ScanConfig(rounds_per_scan=R)``) folds R whole rounds into ONE jitted
``lax.scan``: each scan step gathers its cohort from the resident corpus
(:meth:`repro.data.corpus.ClientCorpus.traced_cohort`), runs the
(sharded) ClientUpdate fan-out, *speculates the verdict on device* with
the traced float32 judge (``core.judgment.judge``; ``spec_backend=
"pallas"`` tiles the class axis through ``entropy_judge_sweep``), and
aggregates against the speculative mask — params are the scan carry, so
the host is touched exactly once per R rounds.

**Selection** (``ScanConfig.selection``):

* ``"replay"`` (default): the host pre-draws all R cohorts from the real
  ``UniformSelector`` before launching the scan and feeds them in as the
  scan's xs. Valid because a uniform draw is verdict-independent and its
  ``update`` is a no-op — the selector stream is *exactly* the stream
  the sequential ``Server`` would have drawn, which is what keeps golden
  histories equal.
* ``"device"``: a true on-device draw — the scan carries a JAX PRNG key
  and each step selects via ``jax.random.choice(..., replace=False)``.
  Histories then follow the device stream (reproducible per seed, but
  *not* comparable to the numpy selector's), so this mode is opt-in.

**Traced pool carry**: the paper's eps-greedy pools (the ``fedentropy``
default) couple each draw to the previous round's verdict, which used to
force R=1. A :class:`repro.fl.selectors.TracedPoolSelector`
(``selector="pools-traced"``) instead folds: the scan carries the pool
membership masks plus a ``jax.random`` key, each step draws via
:func:`repro.core.pools.pools_draw` and re-files via
:func:`~repro.core.pools.pools_refile` against the *speculated* verdict,
and the host selector mirror replays the confirmed draws
(:meth:`~repro.fl.selectors.TracedPoolSelector.fold_drawn` + ``update``)
so a folded block and the sequential ``Server`` walk identical selector
state — bit-for-bit equal histories. A misspeculated round truncates the
pool carry exactly like params: rounds after the first mismatch ran
against a wrong pool state and are discarded, the host mirror re-files
from the float64 oracle, and the continuation scan restarts from the
mirrored masks and the recorded post-draw key. (``selection`` is ignored
while pools fold — the pool draw *is* the on-device selection.)

**Memory** (``ScanConfig.params_mode``):

* ``"stack"`` (default): the scan's ys stack the post-round params every
  round — R rewind points, O(R * |params|) device memory. Fine for the
  paper CNN; fatal for LM pytrees.
* ``"remat"``: ys carry only the O(cohort * num_classes) verdict inputs
  (soft labels, sizes, selections, masks); on a mismatch at round j the
  rewind point is *rematerialized* by re-running rounds 0..j-1 through
  the same compiled step from the block's start carry — bounded
  recompute (< one extra block, only on the rare mismatch) instead of
  the R-fold params stash. Bitwise identical to ``"stack"``: the replay
  runs the identical ops on the identical inputs.

**Oracle replay** (the same bit-for-bit contract as ``PipelinedServer``):
after each scan the host casts the R stacked soft-label matrices to
float64 and replays the verdicts through the composition's own judge.
Recorded verdicts/entropy always come from that oracle. Rounds whose
speculative mask matches are confirmed wholesale (``spec_hit=True``); at
the first mismatch the block truncates — params rewind to the last
confirmed round's output, the mismatched round re-runs *eagerly* from
the oracle verdict exactly as the sequential ``Server`` would
(``spec_hit=False``), and the remaining rounds re-enter a fresh
(shorter) scan whose confirmed rounds carry ``redispatched=True``.

**Eligibility**: folding R>1 rounds without host contact requires every
per-round host dependency to be absent — a verdict-independent or traced
selector (``UniformSelector`` pre-draws; ``TracedPoolSelector`` folds;
the numpy ``PoolSelector``/queue/grouping selectors stay host-coupled),
a stateless strategy, no group dispatch (``prepare_round``), a traced
judge, and a resident data plane. Anything else falls back to
``rounds_per_scan=1`` — plain sequential rounds — with one loud log plus
machine-readable reasons (:attr:`ScanServer.fallback_reasons`, surfaced
in :meth:`ScanServer.stats` and on every fallback round's history record
under ``"scan_fallback"``), so every composition still *runs* under
``engine="scan"``; it just doesn't fold.

Block semantics: ``round()`` still returns one record at a time, but
params advance a whole block at once — an ``evaluate()`` between two
``round()`` calls of the same block sees the block-end model. Run
multiples of R rounds when comparing parameters mid-stream.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...core.pools import pools_draw, pools_refile
from .. import spans
from ..registry import register
from ..selectors import TracedPoolSelector, UniformSelector
from .engine import PipelinedServer, RuntimeConfig

log = logging.getLogger(__name__)

_SELECTION = ("replay", "device")
_PARAMS_MODES = ("stack", "remat")


@dataclass(frozen=True)
class ScanConfig:
    """Knobs for :class:`ScanServer` (the ``engine="scan"`` analog of
    ``RuntimeConfig``); R=1 reduces to the sequential ``Server`` exactly."""
    rounds_per_scan: int = 4      # R rounds folded per host surfacing
    spec_backend: str = "xla"     # traced in-scan judge: "xla" | "pallas"
    selection: str = "replay"     # "replay" (host pre-draw) | "device"
    params_mode: str = "stack"    # rewind points: "stack" ys | "remat" replay
    shard: object = "auto"        # forwarded to the inherited client fan-out
    donate_data: bool = True      # forwarded to the inherited client fan-out

    def __post_init__(self):
        if self.rounds_per_scan < 1:
            raise ValueError("rounds_per_scan must be >= 1")
        if self.selection not in _SELECTION:
            raise ValueError(f"unknown selection {self.selection!r}; "
                             f"expected one of {_SELECTION}")
        if self.params_mode not in _PARAMS_MODES:
            raise ValueError(f"unknown params_mode {self.params_mode!r}; "
                             f"expected one of {_PARAMS_MODES}")


@register("engine", "scan")
class ScanServer(PipelinedServer):
    """R-round ``lax.scan`` drop-in for ``Server`` (same composition axes).
    """

    runtime_cls = ScanConfig

    def __init__(self, *args, runtime: ScanConfig | None = None,
                 mesh=None, **kwargs):
        cfg = runtime if runtime is not None else ScanConfig()
        if not isinstance(cfg, ScanConfig):
            raise ValueError(
                f"ScanServer expects runtime=ScanConfig, got "
                f"{type(cfg).__name__} — RuntimeConfig belongs to the "
                "sequential/pipelined engines, AsyncConfig to async")
        # inherit the pipelined engine's sharded client fan-out and traced
        # judge; verdict speculation lives inside the scan, so the
        # pipelined per-round speculation stays off
        super().__init__(*args, runtime=RuntimeConfig(
            speculate=False, shard=cfg.shard,
            spec_backend=cfg.spec_backend, donate_data=cfg.donate_data),
            mesh=mesh, **kwargs)
        self.scan_config = cfg
        self._ready: list[dict] = []      # oracle-confirmed, un-popped recs
        self._scan_rounds: int | None = None   # resolved R_eff, once
        self.fallback_reasons: list[dict] | None = None  # set on resolve
        self._blocks = 0                  # scan programs launched
        self._mismatch_rounds = 0         # rounds replayed off the oracle
        self._key = (jax.random.PRNGKey(self.config.seed)
                     if cfg.selection == "device" else None)

    # -------------------------------------------------------- eligibility
    def _pool_fold(self) -> bool:
        """True when the selector is the traced-pools kind the scan can
        carry on device (exact class: a subclass may override semantics
        the fold replays)."""
        return type(self.selector) is TracedPoolSelector

    def scan_rounds(self) -> int:
        """Effective R: ``rounds_per_scan`` when the composition can fold,
        else 1 (sequential rounds; one loud log per server)."""
        if self._scan_rounds is None:
            self._scan_rounds = self._resolve_scan_rounds()
        return self._scan_rounds

    def _resolve_scan_rounds(self) -> int:
        R = self.scan_config.rounds_per_scan
        reasons: list[dict] = []
        if (type(self.selector) is not UniformSelector
                and not self._pool_fold()):
            reasons.append({
                "code": "verdict-coupled-selector",
                "component": type(self.selector).__name__,
                "detail": "the selector couples the next draw to the "
                          "previous verdict host-side; only "
                          "UniformSelector (verdict-independent) or "
                          "TracedPoolSelector (selector=\"pools-traced\", "
                          "the device-carried eps-greedy pools) fold"})
        if self.state is not None:
            reasons.append({
                "code": "stateful-strategy",
                "component": type(self.strategy).__name__,
                "detail": "the strategy carries cross-round client state "
                          "the scan cannot checkpoint per round"})
        if getattr(self.strategy, "prepare_round", None) is not None:
            reasons.append({
                "code": "group-dispatch",
                "component": type(self.strategy).__name__,
                "detail": "the strategy lays out whole device groups per "
                          "round (prepare_round)"})
        if not hasattr(self.corpus, "traced_cohort"):
            reasons.append({
                "code": "host-data-plane",
                "component": type(self.corpus).__name__,
                "detail": "the data plane has no traced gather (the "
                          "streaming HostCorpus gathers host-side)"})
        if self._traced_judge_fn() is None:
            reasons.append({
                "code": "untraced-judge",
                "component": type(self.judge).__name__,
                "detail": "the judge has no traced form"})
        if getattr(self, "bank", None) is not None:
            reasons.append({
                "code": "cluster-dispatch",
                "component": type(self.cluster).__name__,
                "detail": "clustered rounds assign clients to ModelBank "
                          "centers host-side every round (argmin over "
                          "jitted scores) and judge per cluster; the "
                          "scan cannot carry the K-center bank through "
                          "a host-free fold"})
        if self._drift:
            reasons.append({
                "code": "drift-schedule",
                "component": "DriftEvent",
                "detail": "a drift schedule rebuilds the corpus "
                          "mid-training; the scan's compiled step "
                          "captures the corpus at trace time, so folded "
                          "rounds would silently train on pre-drift "
                          "data"})
        self.fallback_reasons = reasons
        if R == 1:
            return 1
        if reasons:
            log.warning(
                "scan engine: falling back to rounds_per_scan=1 "
                "(sequential rounds) — %s",
                "; ".join(f"[{r['code']}] {r['component']}: {r['detail']}"
                          for r in reasons))
            return 1
        return R

    def stats(self) -> dict:
        """Machine-readable engine state: the effective fold depth, why a
        fold was refused (``fallback_reasons``, empty when folding), the
        memory mode, and block/mismatch counters."""
        self.scan_rounds()                       # resolve reasons once
        sel_stats = getattr(self.selector, "stats", dict)()
        return {
            "engine": "scan",
            "rounds_per_scan": self.scan_config.rounds_per_scan,
            "effective_rounds_per_scan": self.scan_rounds(),
            "fallback_reasons": [dict(r) for r in self.fallback_reasons],
            "params_mode": self.scan_config.params_mode,
            "selection": self.scan_config.selection,
            "pool_fold": self._pool_fold(),
            "blocks": self._blocks,
            "mismatch_rounds": self._mismatch_rounds,
            "selector": sel_stats,
        }

    # ------------------------------------------------------- scan program
    def _scan_fn(self, r: int):
        """One jitted program running ``r`` speculative rounds.

        ``block(params, key, pos, neg, rows) -> (params, key, pos, neg,
        ys)`` where ``rows`` is the (r, m) pre-drawn selection matrix
        (replay mode; inert otherwise), ``pos``/``neg`` are the pool
        membership masks (pool-fold mode; zero-length placeholders
        otherwise, which XLA drops), and ys stacks per round: the
        selection, raw soft labels + sizes (for the float64 oracle), the
        speculative mask, the post-draw PRNG key (pool-fold/device
        modes), and — in ``params_mode="stack"`` only — the post-round
        params (the truncation rewind points; ``"remat"`` rematerializes
        them on demand instead).
        """
        client = self._client_fn()        # shards the corpus if needed
        spec_fn = self._traced_judge_fn()
        agg = self.aggregator
        corpus = self.corpus
        pool_fold = self._pool_fold()
        on_device_sel = (self.scan_config.selection == "device"
                         and not pool_fold)
        stack_params = self.scan_config.params_mode == "stack"
        n_clients = self.config.num_clients
        m = min(self.config.cohort_size(), n_clients)
        eps = self.selector.eps if pool_fold else 0.0
        key = (("roundscan", r, self.scan_config.selection,
                self.scan_config.params_mode, pool_fold, eps,
                self.runtime.spec_backend, self.aggregator,
                self._shard_enabled()) + self._client_key())

        def make():
            def step(carry, xs):
                params, k, pos, neg = carry
                if pool_fold:
                    sel, k = pools_draw(k, pos, neg, num=m, eps=eps)
                elif on_device_sel:
                    k, sub = jax.random.split(k)
                    sel = jax.random.choice(
                        sub, n_clients, shape=(m,),
                        replace=False).astype(jnp.int32)
                else:
                    sel = xs
                data = corpus.traced_cohort(sel)
                out = client(params, data, None, None, None)
                sizes32 = out["size"].astype(jnp.float32)
                jr = spec_fn(out["soft_label"].astype(jnp.float32), sizes32)
                new_params = agg(params, out, sizes32, jr.mask)
                if pool_fold:
                    pos, neg = pools_refile(pos, neg, sel, jr.mask)
                ys = {"sel": sel, "soft": out["soft_label"],
                      "size": out["size"], "mask": jr.mask}
                if stack_params:
                    ys["params"] = new_params
                if pool_fold or on_device_sel:
                    ys["key"] = k
                return (new_params, k, pos, neg), ys

            def block(params, k, pos, neg, rows):
                (params, k, pos, neg), ys = jax.lax.scan(
                    step, (params, k, pos, neg), rows, length=r)
                return params, k, pos, neg, ys

            return jax.jit(block)
        return self._compile_cache().get(key, make)

    # ------------------------------------------------- memory introspection
    def block_ys_shapes(self, r: int | None = None) -> dict:
        """The stacked-ys pytree of a depth-``r`` block as
        ``jax.ShapeDtypeStruct`` leaves (via ``jax.eval_shape`` — nothing
        runs). ``params_mode="remat"`` blocks have no ``"params"`` entry:
        the per-round footprint is O(cohort * num_classes), independent
        of the model size."""
        R = int(r) if r is not None else self.scan_rounds()
        num = min(self.config.cohort_size(), self.config.num_clients)
        key, pos, neg = self._fold_state()
        rows = jnp.zeros((R, num), jnp.int32)
        out = jax.eval_shape(self._scan_fn(R), self.global_params,
                             key, pos, neg, rows)
        return out[4]

    def stacked_ys_nbytes(self, r: int | None = None) -> int:
        """Device bytes a depth-``r`` block's stacked ys would pin."""
        return int(sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for s in jax.tree.leaves(self.block_ys_shapes(r))))

    def _fold_state(self):
        """(key, pos_mask, neg_mask) carry for the current mode."""
        if self._pool_fold():
            return self.selector.fold_carry()
        dummy = jnp.zeros((0,), jnp.float32)
        key = (self._key if self._key is not None
               else jax.random.PRNGKey(0))          # inert in replay mode
        return key, dummy, dummy

    # ------------------------------------------------------------- rounds
    def round(self) -> dict:
        """One Alg. 2 round record; runs a whole R-round block when the
        confirmed-record buffer is empty."""
        if not self._ready:
            R = self.scan_rounds()
            if R == 1:
                rec = super().round()     # sequential (sharded) round
                if self.fallback_reasons:
                    # machine-readable on the record too (stats() has the
                    # full detail); extra keys are ignored by the golden
                    # comparators
                    rec["scan_fallback"] = [
                        r["code"] for r in self.fallback_reasons]
                return rec
            self._run_block(R)
        rec = self._ready.pop(0)
        self.history.append(rec)
        self.round_idx += 1
        return rec

    def _run_block(self, R: int) -> None:
        cfg = self.config
        num = min(cfg.cohort_size(), cfg.num_clients)
        base = self.round_idx
        pool_fold = self._pool_fold()
        replay = (self.scan_config.selection == "replay"
                  and not pool_fold)
        remat = self.scan_config.params_mode == "remat"
        if replay:
            # pre-draw all R cohorts from the REAL selector: uniform draws
            # are verdict-independent and update() is a no-op, so this is
            # the exact stream the sequential interleaving would produce
            rows = np.stack([np.asarray(self.selector.select(num), np.int32)
                             for _ in range(R)])
        else:
            rows = np.zeros((R, num), np.int32)   # inert xs
        done = 0
        redispatched = False    # rounds re-scanned after a truncation
        params = self.global_params
        while done < R:
            r = R - done
            key, pos, neg = self._fold_state()
            seg = (params, key, pos, neg)     # remat rewind anchor
            seg_rows = jnp.asarray(rows[done:])
            params_out, key_out, pos_out, neg_out, ys = self._scan_fn(r)(
                params, key, pos, neg, seg_rows)
            self._blocks += 1
            soft_all = spans.fetch(ys["soft"], np.float64)
            sizes_all = spans.fetch(ys["size"], np.float64)
            masks_all = spans.fetch(ys["mask"])
            sels_all = spans.fetch(ys["sel"])
            keys_all = ys.get("key")

            mismatch_at = None
            for j in range(r):
                sel = [int(c) for c in sels_all[j]]
                verdict = self._verdict(soft_all[j], sizes_all[j], sel)
                if not np.array_equal(verdict[0], masks_all[j]):
                    mismatch_at = j
                    break
                if pool_fold:
                    # mirror the confirmed in-scan draw, then re-file —
                    # the exact sequential select/update cycle
                    self.selector.fold_drawn(sels_all[j], keys_all[j])
                rec = self._record(sel, verdict, soft_all.shape[-1],
                                   spec_hit=True, redispatched=redispatched)
                rec["round"] = base + done + j
                self._ready.append(rec)

            if mismatch_at is None:
                params = params_out
                if not replay:
                    self._key = key_out
                done += r
                continue

            # --- truncate: rewind params to the last confirmed round and
            #     redo the mismatched round eagerly from the oracle, then
            #     re-scan whatever rounds remain -------------------------
            j = mismatch_at
            self._mismatch_rounds += 1
            if j > 0:
                if remat:
                    # rematerialize the rewind point: re-run the j
                    # confirmed rounds through the SAME compiled step from
                    # the block's start carry — identical ops on identical
                    # inputs, so the result is bitwise the stacked
                    # ys["params"][j-1] of params_mode="stack"
                    params = self._scan_fn(j)(*seg, seg_rows[:j])[0]
                else:
                    params = jax.tree.map(lambda x: x[j - 1], ys["params"])
            if pool_fold:
                # the mismatched round's DRAW is valid (it depended only
                # on confirmed state); mirror it so the eager oracle
                # round's update() re-files against the right removal,
                # and adopt the post-draw key for the continuation
                self.selector.fold_drawn(sels_all[j], keys_all[j])
            elif not replay:
                # the continuation's draws chain from the carry key as it
                # stood AFTER round j's split
                self._key = ys["key"][j]
            # the sequential round after selection, from the rewind point
            rec = self._play([int(c) for c in sels_all[j]], start=params,
                             spec_hit=False, redispatched=False)
            rec["round"] = base + done + j
            self._ready.append(rec)
            params = self.global_params
            done += j + 1
            redispatched = True
        self.global_params = params
