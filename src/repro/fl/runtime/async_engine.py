"""The async buffered round engine: FedBuff-style streaming + judgment.

Both round-synchronous engines gate every aggregation on the slowest
client in the cohort. ``AsyncBufferedServer`` drops that barrier: clients
stream their finished updates under a deterministic *simulated* arrival
clock (a seeded per-client latency model — pure virtual time, never the
wall clock), each arriving update passes the paper's max-entropy judgment
as an **admission filter** against the already-admitted buffer
(:meth:`repro.fl.judges.MaxEntropyJudge.admit` — buffered rows are
protected: their weights already shipped), and the server aggregates a
*flush* whenever ``AsyncConfig.buffer_size`` arrivals have been screened.
Admitted updates aggregate with staleness-damped weights (FedBuff's
polynomial damping ``(1 + τ)^-α`` with τ = flushes elapsed since the
update's model version); rejected updates are dropped *before* shipping
weights — the paper's "don't collect harmful models" rule applied
per-arrival, which is where the uplink savings over round-synchronous
FedAvg come from (see ``benchmarks/async_throughput.py``).

The engine reuses the whole existing data plane: cohorts are dispatched
through the device-resident ``ClientCorpus`` gather and the pipelined
engine's shard_map client fan-out (it subclasses ``PipelinedServer`` for
exactly that ``_client_fn``), so a dispatch is one on-device gather +
vmapped/sharded ClientUpdate regardless of mesh size.

**Reduction guarantee** (tested bit-for-bit in tests/test_async_engine.py
against both a live sequential ``Server`` and the recorded goldens): with
``buffer_size = |cohort|``, the zero-latency clock, and damping off, every
dispatch arrives as one simultaneous batch, admission over the empty
buffer *is* the sequential round judgment (float64 oracle), and the flush
runs ``Server.round``'s own merge stage (aggregate, state, selector,
record) — so histories and parameters equal the sequential engine's
exactly.

Determinism: the only random streams are the selector's (advanced exactly
once per dispatched cohort) and the latency model's own
``np.random.default_rng(AsyncConfig.seed)``; arrival ties break by
dispatch order. Same seeds → identical flush histories, always.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .. import spans
from ..judges import admit_candidates
from ..registry import register
from .engine import PipelinedServer, RuntimeConfig

_CLOCKS = ("zero", "uniform", "straggler")


@dataclass(frozen=True)
class AsyncConfig:
    """Knobs for :class:`AsyncBufferedServer` (the ``engine="async"``
    analog of ``RuntimeConfig``; the defaults reduce to the sequential
    ``Server`` exactly — see the module docstring)."""
    buffer_size: int = 0          # K screened arrivals per flush; 0=|cohort|
    staleness_alpha: float = 0.0  # (1+τ)^-α damping; 0 disables exactly
    clock: str = "zero"           # "zero" | "uniform" | "straggler"
    latency_scale: float = 1.0    # mean-ish per-update latency (virtual)
    straggler_frac: float = 0.125  # fraction of clients that straggle
    straggler_factor: float = 16.0  # stragglers' latency multiplier
    seed: int = 0                 # latency model stream (not the selector's)
    concurrency: int = 0          # in-flight update target; 0=|cohort|
    shard: object = "auto"        # forwarded to the inherited client fan-out
    donate_data: bool = True      # forwarded to the inherited client fan-out

    def __post_init__(self):
        if self.clock not in _CLOCKS:
            raise ValueError(
                f"unknown clock {self.clock!r}; expected one of {_CLOCKS}")
        if self.buffer_size < 0:
            raise ValueError("buffer_size must be >= 0 (0 = cohort size)")
        if self.staleness_alpha < 0:
            raise ValueError("staleness_alpha must be >= 0")
        if self.latency_scale < 0:
            raise ValueError("latency_scale must be >= 0")
        if not 0.0 <= self.straggler_frac <= 1.0:
            raise ValueError("straggler_frac must be in [0, 1]")
        if self.straggler_factor < 1.0:
            raise ValueError("straggler_factor must be >= 1")
        if self.concurrency < 0:
            raise ValueError("concurrency must be >= 0 (0 = cohort size)")


def staleness_weights(tau, alpha: float) -> np.ndarray:
    """FedBuff's polynomial staleness damping: ``(1 + τ)^-α`` (float64).

    Monotone non-increasing in τ for α > 0; identically 1 at α = 0
    (tests/test_async_properties.py holds both by property).
    """
    tau = np.asarray(tau, np.float64)
    if np.any(tau < 0):
        raise ValueError("staleness must be >= 0")
    return np.power(1.0 + tau, -float(alpha))


class ArrivalClock:
    """Deterministic per-client latency model over *virtual* time.

    Latencies are drawn once at construction from
    ``np.random.default_rng(cfg.seed)`` — "zero" is all-zeros (every
    dispatch arrives instantly, as one batch), "uniform" is
    ``latency_scale * U(0.5, 1.5)`` per client, and "straggler" starts
    from uniform then multiplies a ``straggler_frac`` subset by
    ``straggler_factor`` (the heavy-tail IoT regime the benchmarks
    stress). An update dispatched at virtual time t arrives at
    ``t + latency[client]`` — no wall-clock reads anywhere.
    """

    def __init__(self, cfg: AsyncConfig, num_clients: int):
        rng = np.random.default_rng(cfg.seed)
        if cfg.clock == "zero":
            lat = np.zeros(num_clients, np.float64)
        else:
            lat = cfg.latency_scale * rng.uniform(0.5, 1.5, num_clients)
            if cfg.clock == "straggler":
                k = int(round(cfg.straggler_frac * num_clients))
                if k:
                    slow = rng.choice(num_clients, size=k, replace=False)
                    lat[slow] *= cfg.straggler_factor
        self.latency = lat

    def arrival(self, client: int, t_dispatch: float) -> float:
        return float(t_dispatch + self.latency[client])


@register("engine", "async")
class AsyncBufferedServer(PipelinedServer):
    """Streaming drop-in for ``Server``: ``round()`` == one buffer flush."""

    runtime_cls = AsyncConfig

    def __init__(self, *args, runtime: AsyncConfig | None = None,
                 mesh=None, **kwargs):
        cfg = runtime if runtime is not None else AsyncConfig()
        if not isinstance(cfg, AsyncConfig):
            raise ValueError(
                f"AsyncBufferedServer expects runtime=AsyncConfig, got "
                f"{type(cfg).__name__} — RuntimeConfig belongs to the "
                "sequential/pipelined engines")
        # inherit the pipelined engine's sharded client fan-out; the async
        # engine replaces round structure, not client compute, so verdict
        # speculation never applies here
        super().__init__(*args, runtime=RuntimeConfig(
            speculate=False, shard=cfg.shard, donate_data=cfg.donate_data),
            mesh=mesh, **kwargs)
        if getattr(self.strategy, "prepare_round", None) is not None:
            raise ValueError(
                f"{type(self.strategy).__name__} lays out whole device "
                "groups per round (prepare_round); the async engine "
                "screens single arrivals and cannot honor group dispatch "
                "yet — use the sequential or pipelined engine (async + "
                "fedcat groups is a recorded ROADMAP follow-up)")
        if getattr(self, "bank", None) is not None:
            raise ValueError(
                f"{type(self.cluster).__name__} carries a K-center "
                "ModelBank; the async engine's per-arrival admission has "
                "no per-cluster buffer semantics yet — use the sequential "
                "or pipelined engine (async + clusters is a recorded "
                "ROADMAP follow-up)")
        if self._drift:
            raise ValueError(
                "the async engine's in-flight arrival heap holds updates "
                "computed against the dispatch-time corpus; a drift "
                "schedule would mix pre- and post-drift arrivals in one "
                "flush — use the sequential or pipelined engine for "
                "drifted runs")
        self.async_config = cfg
        self.clock = ArrivalClock(cfg, self.config.num_clients)
        self._events: list[tuple] = []   # heap of (t_arrival, seq, entry)
        self._seq = 0                    # global dispatch counter (tiebreak)
        self._vtime = 0.0                # virtual now = last arrival seen
        self._buffer: list[dict] = []    # admitted, not yet flushed
        self._flush_log: list[dict] = []  # screened this window, arrival order
        self._pos_log: list[int] = []    # admitted client ids, arrival order
        self._neg_log: list[int] = []    # rejected client ids, removal order
        self._last_ent = float("nan")    # entropy after latest screening

    # ------------------------------------------------------------- sizing
    def _cohort_size(self) -> int:
        return self.config.cohort_size()

    @property
    def buffer_size(self) -> int:
        k = self.async_config.buffer_size
        return k if k > 0 else self._cohort_size()

    def _concurrency_target(self) -> int:
        c = self.async_config.concurrency
        return c if c > 0 else self._cohort_size()

    # ------------------------------------------------------------- stream
    def _dispatch_cohort(self) -> None:
        """Select a cohort, launch its (sharded) client compute, and put
        each member's finished update on the arrival heap.

        The dispatch unit stays a full cohort — one compiled program shape,
        one on-device corpus gather — but arrivals are *per client*: each
        row of the cohort output becomes its own event at
        ``vtime + latency[client]``, stamped with the current model version
        for staleness accounting. Soft labels sync to host here (they ship
        with every selected client in the comm model; only admitted clients
        later ship weights).
        """
        with TraceAnnotation(spans.SELECT):
            sel = self.selector.select(self._cohort_size())
        out = self._run_cohort(sel, self.selector)
        soft, sizes = self._readback(out)
        for row, client in enumerate(sel):
            entry = {"client": int(client), "row": row, "out": out,
                     "soft": soft[row], "size": float(sizes[row]),
                     "version": self.round_idx, "seq": self._seq,
                     "t_arr": self.clock.arrival(client, self._vtime)}
            heapq.heappush(self._events, (entry["t_arr"], self._seq, entry))
            self._seq += 1

    def _ensure_inflight(self) -> None:
        target = self._concurrency_target()
        while len(self._events) < target:
            self._dispatch_cohort()

    def _pop_batch(self) -> list[dict]:
        """Pop every event sharing the next arrival instant (ties break by
        dispatch order, so the zero-latency clock yields whole cohorts in
        selection order — the reduction case)."""
        t, _, entry = heapq.heappop(self._events)
        self._vtime = max(self._vtime, t)
        batch = [entry]
        while self._events and self._events[0][0] == t:
            batch.append(heapq.heappop(self._events)[2])
        return batch

    def _screen(self, batch: list[dict]) -> None:
        """Max-entropy admission of one arrival batch against the buffer."""
        cand_soft = np.stack([e["soft"] for e in batch])
        cand_sizes = np.asarray([e["size"] for e in batch], np.float64)
        if self._buffer:
            buf_soft = np.stack([e["soft"] for e in self._buffer])
            buf_sizes = np.asarray([e["size"] for e in self._buffer],
                                   np.float64)
        else:
            buf_soft = np.zeros((0, cand_soft.shape[1]), np.float64)
            buf_sizes = np.zeros((0,), np.float64)
        admit = getattr(self.judge, "admit", None)
        with TraceAnnotation(spans.JUDGE):
            if admit is None:
                a_rel, r_rel, ent = admit_candidates(
                    self.judge, buf_soft, buf_sizes, cand_soft, cand_sizes)
            else:
                a_rel, r_rel, ent = admit(buf_soft, buf_sizes,
                                          cand_soft, cand_sizes)
        admitted = set(a_rel)
        for i, entry in enumerate(batch):
            entry["admitted"] = i in admitted
            self._flush_log.append(entry)
        self._buffer.extend(batch[i] for i in a_rel)
        self._pos_log.extend(batch[i]["client"] for i in a_rel)
        self._neg_log.extend(batch[i]["client"] for i in r_rel)
        self._last_ent = ent

    # -------------------------------------------------------------- flush
    def _flush(self) -> dict:
        """Aggregate the screened window through ``Server``'s merge stage
        over the arrival-ordered rows, so the K=|cohort| zero-latency case
        is bit-for-bit the sequential round."""
        log = self._flush_log
        sel = [e["client"] for e in log]
        rows = [jax.tree.map(lambda x, r=e["row"]: x[r], e["out"])
                for e in log]
        out = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        sizes = np.asarray([e["size"] for e in log], np.float64)
        mask = np.asarray([1.0 if e["admitted"] else 0.0 for e in log],
                          np.float32)
        tau = np.asarray([self.round_idx - e["version"] for e in log],
                         np.int64)
        alpha = self.async_config.staleness_alpha
        # α==0 skips the damping multiply entirely: the reduction must hand
        # the aggregator the float64 sizes Server.round hands it, untouched
        weights = sizes if alpha == 0.0 else \
            sizes * staleness_weights(tau, alpha)

        verdict = (mask, self._pos_log, self._neg_log, self._last_ent, None)
        rec = self._merge(
            self.global_params, out, weights, verdict, sel,
            # async extras: the sequential record plus stream telemetry
            flush_time=float(self._vtime),
            staleness=[int(t) for t in tau],
            buffer_occupancy=len(self._buffer),
            inflight=len(self._events),
            seq=[e["seq"] for e in log],
            admitted_seq=[e["seq"] for e in log if e["admitted"]])
        # staleness feedback plumbing: selectors exposing
        # ``observe_staleness`` see each screened arrival's τ (flushes
        # elapsed since its dispatch version) alongside the verdict, in
        # arrival order — the hook a staleness-aware selector would rank
        # on. Pure observation: no built-in selector defines it, so the
        # default stream (and the sequential reduction) is untouched.
        observe = getattr(self.selector, "observe_staleness", None)
        if observe is not None:
            observe([{"client": e["client"], "staleness": int(t),
                      "admitted": bool(e["admitted"])}
                     for e, t in zip(log, tau)])
        self._commit(rec)
        self._buffer, self._flush_log = [], []
        self._pos_log, self._neg_log = [], []
        self._last_ent = float("nan")
        return rec

    # ------------------------------------------------------------- rounds
    def round(self) -> dict:
        """Advance virtual time until ``buffer_size`` arrivals have been
        screened, then flush. A simultaneous arrival batch is screened
        whole, so a flush can exceed K by the tie overshoot (the zero
        clock flushes exact cohorts)."""
        k = self.buffer_size
        with TraceAnnotation(spans.ROUND, round=self.round_idx):
            while len(self._flush_log) < k:
                self._ensure_inflight()
                self._screen(self._pop_batch())
            return self._flush()
