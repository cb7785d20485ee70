"""Path ``fleet_server``: the paper's round through ``repro.fl.build``'s
default engine (the sequential ``Server``: host pools, float64 maxent
judge, weighted aggregator) on the device-resident data plane.

One timed step is one ``Server.round()``. The window drives the object
that set-up built and ran through its first rounds.
"""
from __future__ import annotations

import gc

import numpy as np

from .. import harness, traffic
from ..reference import fl as ref

NUMBERS = ("selection_mismatch", "size_mismatch", "verdict_mismatch",
           "soft_label_gap", "soft_label_median_gap", "client_change_gap",
           "model_change_gap")
CHECK_ROUNDS = 3


class _RecordingJudge:
    """Wraps the composition's judge to keep what it was given and what it
    said, for the first rounds only."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = []

    def __call__(self, soft, sizes):
        kept, removed, ent = self.inner(soft, sizes)
        self.calls.append((np.array(soft, np.float64),
                           np.array(sizes, np.float64), list(kept),
                           list(removed)))
        return kept, removed, ent


class _RecordingAggregator:
    """Wraps the composition's aggregator to keep, for the first round,
    each client's per-leaf norm of its change from the global model it
    started from."""

    def __init__(self, inner):
        import jax
        import jax.numpy as jnp
        self.inner = inner
        self.first = None
        self._norms = jax.jit(lambda g, stacked: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                b - a[None]), axis=tuple(range(1, b.ndim)))), g, stacked))

    def __call__(self, global_params, out, sizes, mask):
        if self.first is None:
            norms = self._norms(global_params, out["params"])
            self.first = {k: np.asarray(v, np.float64) for k, v in
                          ref.from_program(norms).items()}
        return self.inner(global_params, out, sizes, mask)


class Driver:
    spans = ("round",)

    def __init__(self, cfg: dict, wl: dict, seed: int):
        import jax
        import repro.fl as fl
        from repro.models import cnn
        self.cfg, self.wl, self.seed = cfg, wl, seed
        self.data, self.host = traffic.fleet_data(cfg, wl["traffic"], seed)
        params = ref.to_program(ref.cnn_init(traffic.jax_key(seed), cfg))
        config = fl.ServerConfig(num_clients=cfg["num_clients"],
                                 participation=cfg["participation"],
                                 eps=cfg["eps"], seed=seed)
        local = fl.LocalSpec(epochs=cfg["local_epochs"],
                             batch_size=cfg["batch_size"], lr=cfg["lr"],
                             momentum=cfg["momentum"])
        self.server = fl.build("fedentropy", cnn.apply, params, self.data,
                               config, local, data_plane="resident")
        self.server.judge = _RecordingJudge(self.server.judge)
        self.server.aggregator = _RecordingAggregator(self.server.aggregator)
        self._sizes = self.host["counts"].sum(1)
        self._flops = harness.flops_module(cfg["name"]).round_flops
        self.round_flops: list[float] = []
        self._jax = jax

    def step(self) -> None:
        with self._jax.profiler.TraceAnnotation("round"):
            rec = self.server.round()
        # model FLOPs of the round: its cohort's real images
        self.round_flops.append(
            self._flops(self.cfg, self._sizes[rec["selected"]].sum()))

    def sync(self) -> None:
        self._jax.block_until_ready(self.server.global_params)

    def capture(self) -> dict:
        """What the check needs from the first rounds; the judge and
        aggregator wrappers come off so that the window runs the
        composition as built."""
        self.sync()
        rec = self.server.judge
        self.server.judge = rec.inner
        agg = self.server.aggregator
        self.server.aggregator = agg.inner
        hist = self.server.history[:CHECK_ROUNDS]
        return {
            "selected": [list(h["selected"]) for h in hist],
            "verdict": [(list(h["positive"]), list(h["negative"]))
                        for h in hist],
            "judged": rec.calls[:CHECK_ROUNDS],
            "sizes": [c[1] for c in rec.calls[:CHECK_ROUNDS]],
            "soft": [c[0] for c in rec.calls[:CHECK_ROUNDS]],
            "client_change": agg.first,
            "params": {k: np.asarray(v, np.float32) for k, v in
                       ref.from_program(self.server.global_params).items()},
        }

    def close(self) -> None:
        self.server = self.data = None
        gc.collect()


def _cohort(cfg, seed, host):
    return lambda ids: traffic.some_clients(cfg, seed, host, ids)


def _host(cfg, wl, seed):
    counts = traffic.class_counts(cfg, wl["traffic"])
    labels, valid = traffic.client_labels(
        counts, traffic.stacked_rows(cfg, counts), seed)
    return {"labels": labels, "valid": valid, "counts": counts}


def reference_capture(cfg, wl, seed, *, dtype="float32", fault=None,
                      rounds=CHECK_ROUNDS) -> dict:
    """The reference put in the program's place: the same capture, made
    by the reference itself (its own verdicts re-file its pools)."""
    host = _host(cfg, wl, seed)
    p0 = ref.cnn_init(traffic.jax_key(seed), cfg)
    out = ref.run_rounds(cfg, p0, _cohort(cfg, seed, host),
                         [None] * rounds, seed=seed, rounds=rounds,
                         dtype=dtype, fault=fault)
    cap = {k: out[k] for k in ("selected", "verdict", "judged", "sizes",
                               "soft", "params")}
    return dict(cap, client_change=out["client_change"][0])


def compare(cfg, wl, seed, cap: dict) -> dict:
    """Each compared number of a run's capture against the reference,
    which follows the run's verdicts (checked themselves, exactly)."""
    host = _host(cfg, wl, seed)
    p0 = ref.cnn_init(traffic.jax_key(seed), cfg)
    rounds = len(cap["selected"])
    r = ref.run_rounds(cfg, p0, _cohort(cfg, seed, host), cap["verdict"],
                       seed=seed, rounds=rounds)
    sel = sum(int(a != b) for s, t in zip(cap["selected"], r["selected"])
              for a, b in zip(s, t)) + sum(
        abs(len(s) - len(t)) for s, t in zip(cap["selected"], r["selected"]))
    size = sum(int(np.sum(np.asarray(a) != np.asarray(b)))
               for a, b in zip(cap["sizes"], r["sizes"]))
    verdict = 0
    for soft, sizes, kept, removed in cap["judged"]:
        k, rm, _ = ref.judge(soft, sizes)
        verdict += int(k != kept) + int(rm != removed)
    # each round's clients' largest soft-label entry gaps; the widest is
    # compared on round 1 only, whose clients start from the same weights
    # on both sides, and the median client over all the rounds
    client_gaps = [np.max(np.abs(np.asarray(a) - b), axis=1)
                   for a, b in zip(cap["soft"], r["soft"])]
    soft_gaps = [float(np.max(g)) for g in client_gaps]
    soft_medians = [float(np.median(g)) for g in client_gaps]
    # round 1 only: its clients start from the same weights on both sides
    a, b = cap["client_change"], r["client_change"][0]
    client = max(harness.leaf_gap({k: v[i] for k, v in a.items()},
                                  {k: v[i] for k, v in b.items()},
                                  {k: v[i] for k, v in b.items()})
                 for i in range(len(cap["selected"][0])))
    p0h = {k: np.asarray(v, np.float64) for k, v in p0.items()}
    got = {k: float(np.linalg.norm(cap["params"][k] - p0h[k])) for k in p0h}
    want = {k: float(np.linalg.norm(r["params"][k] - p0h[k])) for k in p0h}
    return {"selection_mismatch": float(sel), "size_mismatch": float(size),
            "verdict_mismatch": float(verdict), "soft_label_gap": soft_gaps[0],
            "soft_label_median_gap": max(soft_medians),
            "client_change_gap": client,
            "model_change_gap": harness.leaf_gap(got, want, want),
            "soft_label_gap_by_round": soft_gaps,
            "soft_label_median_by_round": soft_medians}

