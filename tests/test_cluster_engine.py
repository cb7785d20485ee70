"""Clustered federated learning: the K-center ModelBank axis.

Holds the ISSUE acceptance criteria: ``ifca+maxent`` at K=1 reproduces
the seed golden bit-for-bit (params digest included); the new clustered
golden (K=3, drift at round 2) holds Server == PipelinedServer with
speculation off AND on; the scan engine falls back to R=1 with
machine-readable ``cluster-dispatch``/``drift-schedule`` reasons while
still matching the clustered history; plus deterministic twins of the
hypothesis properties (tests/test_cluster_properties.py) so the
invariants are exercised even where hypothesis isn't installed.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl as fl
from repro.core.strategies import LocalSpec
from repro.data.partition import drift_schedule, partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.models import cnn

GOLDEN_SEED = os.path.join(os.path.dirname(__file__), "golden",
                           "seed_history.json")
GOLDEN_CLUSTER = os.path.join(os.path.dirname(__file__), "golden",
                              "cluster_history.json")

# same tolerance split as tests/test_runtime_engine.py: bitwise on one
# device, entropy tolerance across forced multi-device program shapes
_SINGLE_DEVICE = len(jax.devices()) == 1
ENT_ATOL = 1e-9 if _SINGLE_DEVICE else 1e-6


@pytest.fixture(scope="module")
def tiny():
    """Identical to the setup the golden histories were recorded with."""
    (xtr, ytr), _ = make_image_dataset(
        num_classes=4, train_per_class=60, test_per_class=15, hw=16,
        noise=0.4, seed=0)
    parts = partition("case1", ytr, 8, 4, seed=0)
    data = stack_clients(xtr, ytr, parts, batch_multiple=20)
    params = cnn.init(jax.random.PRNGKey(0), image_hw=16, num_classes=4)
    return (xtr, ytr), data, params


def _params_digest(params) -> float:
    return float(sum(float(jnp.sum(jnp.abs(x)))
                     for x in jax.tree.leaves(params)))


def _drift(tiny, at=2, seed=0):
    (xtr, ytr), data, _ = tiny
    return drift_schedule(xtr, ytr, 8, 4, at=at, seed=seed,
                          samples_per_client=int(data["y"].shape[1]))


def _build(tiny, k=3, drift=None, engine=None, runtime=None,
           name="ifca+maxent", **overrides):
    _, data, params = tiny
    kwargs = dict(overrides)
    if engine is not None:
        kwargs["engine"] = engine
    if runtime is not None:
        kwargs["runtime"] = runtime
    return fl.build(name, cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=0, num_clusters=k),
                    LocalSpec(epochs=1, batch_size=20),
                    drift=drift, **kwargs)


def _assert_matches_cluster_golden(history, golden):
    assert len(history) == len(golden)
    for g, w in zip(history, golden):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["comm"]["total_bytes"] == w["total_bytes"]
        assert g["cluster"] == w["cluster"]
        assert sorted(g["clusters"]) == sorted(w["clusters"])
        for c, v in w["clusters"].items():
            got = g["clusters"][c]
            assert got["members"] == v["members"]
            assert got["positive"] == v["positive"]
            assert got["negative"] == v["negative"]
        ent = float(w["entropy"])
        if np.isnan(ent):
            assert np.isnan(g["entropy"])
        else:
            assert g["entropy"] == pytest.approx(ent, abs=ENT_ATOL)


# ------------------------------------------------------- K=1 reduction
def test_k1_reduces_to_seed_golden(tiny):
    """ISSUE acceptance: ``ifca+maxent`` with num_clusters=1 IS the seed
    ``fedentropy`` run — same history bit-for-bit, same params digest."""
    with open(GOLDEN_SEED) as f:
        want = json.load(f)["fedentropy"]
    server = _build(tiny, k=1)
    assert server.bank is None            # the unclustered code path
    for _ in range(len(want["history"])):
        server.round()
    for g, w in zip(server.history, want["history"]):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["comm"]["total_bytes"] == w["total_bytes"]
        assert "cluster" not in g
        assert g["entropy"] == pytest.approx(float(w["entropy"]),
                                             abs=ENT_ATOL)
    if _SINGLE_DEVICE:
        assert _params_digest(server.global_params) == \
            float(want["params_digest"])


# --------------------------------------------------- golden equivalence
def test_sequential_matches_cluster_golden(tiny):
    with open(GOLDEN_CLUSTER) as f:
        want = json.load(f)["ifca_maxent_k3_drift"]
    server = _build(tiny, k=3, drift=_drift(tiny))
    for _ in range(len(want["history"])):
        server.round()
    _assert_matches_cluster_golden(server.history, want["history"])
    drift_rounds = [r["round"] for r in server.history if "drift" in r]
    assert drift_rounds == [want["drift_round"]]
    if _SINGLE_DEVICE:
        assert _params_digest(server.bank.stacked) == \
            float(want["params_digest"])


@pytest.mark.parametrize("speculate", [False, True])
def test_pipelined_matches_cluster_golden(tiny, speculate):
    """ISSUE acceptance: PipelinedServer holds the clustered golden with
    speculation off AND on (verdicts always from the float64 oracle)."""
    with open(GOLDEN_CLUSTER) as f:
        want = json.load(f)["ifca_maxent_k3_drift"]
    server = _build(tiny, k=3, drift=_drift(tiny), engine="pipelined",
                    runtime=fl.RuntimeConfig(speculate=speculate))
    for _ in range(len(want["history"])):
        server.round()
    _assert_matches_cluster_golden(server.history, want["history"])
    if _SINGLE_DEVICE:
        assert _params_digest(server.bank.stacked) == \
            float(want["params_digest"])
    if speculate:
        assert all("spec_hit" in r for r in server.history)


@pytest.mark.parametrize("speculate", [False, True])
def test_k2_pipelined_matches_live_server(tiny, speculate, same_run):
    """Same host, same process: K=2 clustered rounds (drift at round 2)
    through the pipelined engine equal the sequential ``Server``'s bit
    for bit — records, per-cluster verdicts and every bank leaf."""
    server = _build(tiny, k=2, drift=_drift(tiny))
    assert type(server) is fl.Server
    engine = _build(tiny, k=2, drift=_drift(tiny), engine="pipelined",
                    runtime=fl.RuntimeConfig(speculate=speculate,
                                             shard=False))
    for _ in range(5):
        server.round()
        engine.round()
    same_run(engine, server)
    assert [r["round"] for r in engine.history if "drift" in r] == [2]
    if speculate:
        assert all(isinstance(r["spec_hit"], bool) for r in engine.history)


def test_pipelined_speculation_never_spans_drift(tiny):
    """No pending dispatch may exist when a drift event applies: the
    round before the drift must not speculatively dispatch (spec_next)."""
    server = _build(tiny, k=3, drift=_drift(tiny, at=2), engine="pipelined",
                    runtime=fl.RuntimeConfig(speculate=True))
    server.round()                         # round 0: may speculate round 1
    server.round()                         # round 1: must NOT dispatch 2
    assert server._pending is None
    rec = server.round()                   # round 2: drift applies here
    assert "drift" in rec


def test_fesem_matches_across_engines(tiny):
    """FeSEM's sticky weight-distance assignment walks the same stream
    sequentially and speculatively (update is verdict-independent)."""
    seq = _build(tiny, k=3, name="fesem", judge="maxent",
                 selector="pools")
    pip = _build(tiny, k=3, name="fesem", judge="maxent",
                 selector="pools", engine="pipelined",
                 runtime=fl.RuntimeConfig(speculate=True))
    for _ in range(4):
        seq.round()
        pip.round()
    for a, b in zip(seq.history, pip.history):
        assert a["selected"] == b["selected"]
        assert a["positive"] == b["positive"]
        assert a["cluster"] == b["cluster"]
    assert seq.cluster.stats() == pip.cluster.stats()
    if _SINGLE_DEVICE:
        assert _params_digest(seq.bank.stacked) == \
            _params_digest(pip.bank.stacked)


# ------------------------------------------------------------ scan axis
def test_scan_falls_back_on_clusters_and_drift(tiny):
    """Satellite: the scan engine refuses to fold clustered/drifted runs
    — R=1 eager rounds, machine-readable reasons, history still equal."""
    with open(GOLDEN_CLUSTER) as f:
        want = json.load(f)["ifca_maxent_k3_drift"]
    server = _build(tiny, k=3, drift=_drift(tiny), engine="scan",
                    runtime=fl.ScanConfig(rounds_per_scan=4))
    assert server.scan_rounds() == 1
    codes = {r["code"] for r in server.fallback_reasons}
    assert "cluster-dispatch" in codes
    assert "drift-schedule" in codes
    for _ in range(len(want["history"])):
        rec = server.round()
        assert "cluster-dispatch" in rec["scan_fallback"]
    _assert_matches_cluster_golden(server.history, want["history"])
    stats = server.stats()
    assert stats["effective_rounds_per_scan"] == 1
    assert {r["code"] for r in stats["fallback_reasons"]} >= \
        {"cluster-dispatch", "drift-schedule"}


def test_scan_does_not_flag_unclustered_runs(tiny):
    server = _build(tiny, k=1, name="fedentropy", engine="scan",
                    runtime=fl.ScanConfig(rounds_per_scan=2))
    server.scan_rounds()
    codes = {r["code"] for r in server.fallback_reasons}
    assert "cluster-dispatch" not in codes
    assert "drift-schedule" not in codes


# ------------------------------------------------------- engine refusals
def test_async_refuses_clusters(tiny):
    with pytest.raises(ValueError, match="ModelBank"):
        _build(tiny, k=3, engine="async", runtime=fl.AsyncConfig())


def test_async_refuses_drift(tiny):
    with pytest.raises(ValueError, match="drift"):
        _build(tiny, k=1, name="fedentropy", drift=_drift(tiny),
               engine="async", runtime=fl.AsyncConfig())


def test_clusters_refuse_stateful_strategy(tiny):
    with pytest.raises(ValueError, match="state"):
        _build(tiny, k=3, name="ifca", strategy="scaffold")


def test_clusters_refuse_chain_strategy(tiny):
    with pytest.raises(ValueError, match="fan-out"):
        _build(tiny, k=3, name="ifca", strategy="catchain")


def test_drift_event_validates_sample_length(tiny):
    _, data, params = tiny
    bad = fl.DriftEvent(round=1, clients=(0,),
                        data={"y": np.zeros((1, 3), np.int32)})
    with pytest.raises(ValueError, match="sample length"):
        fl.build("fedentropy", cnn.apply, params, data,
                 fl.ServerConfig(num_clients=8, participation=0.5, seed=0),
                 LocalSpec(epochs=1, batch_size=20), drift=[bad])


# ------------------------------------------ deterministic property twins
def test_drift_schedule_deterministic(tiny):
    """Twin of the hypothesis property: same seed -> identical events;
    different seed -> different drifting sets or rows."""
    a, b = _drift(tiny, seed=0), _drift(tiny, seed=0)
    assert len(a) == len(b) == 1
    assert a[0].round == b[0].round and a[0].clients == b[0].clients
    for k in a[0].data:
        np.testing.assert_array_equal(a[0].data[k], b[0].data[k])
    c = _drift(tiny, seed=7)[0]
    assert (c.clients != a[0].clients
            or any(not np.array_equal(c.data[k], a[0].data[k])
                   for k in c.data))


def test_drift_applies_exactly_once(tiny):
    """No drift before round r; the corpus changes at r and only at r."""
    server = _build(tiny, k=1, name="fedentropy", drift=_drift(tiny, at=2))
    before = {k: np.array(v) for k, v in server.corpus.as_numpy().items()}
    sigs = []
    for _ in range(4):
        server.round()
        sigs.append({k: np.array(v)
                     for k, v in server.corpus.as_numpy().items()})
    # rounds 0,1 ran on the original corpus (drift applies at START of 2)
    for k in before:
        np.testing.assert_array_equal(sigs[0][k], before[k])
        np.testing.assert_array_equal(sigs[1][k], before[k])
        np.testing.assert_array_equal(sigs[2][k], sigs[3][k])
    assert any(not np.array_equal(sigs[2][k], before[k]) for k in before)
    assert server._drift == []


def test_assignment_partitions_cohort(tiny):
    """Every selected client lands in exactly one cluster, ids in [0, K)."""
    server = _build(tiny, k=3)
    for _ in range(3):
        rec = server.round()
        cids = rec["cluster"]
        assert len(cids) == len(rec["selected"])
        assert all(0 <= c < 3 for c in cids)
        members = [m for v in rec["clusters"].values()
                   for m in v["members"]]
        assert sorted(members) == sorted(rec["selected"])


def test_argmin_assign_k1_constant():
    scores = np.abs(np.random.default_rng(0).normal(size=(1, 7)))
    np.testing.assert_array_equal(fl.argmin_assign(scores), np.zeros(7))
    with pytest.raises(ValueError):
        fl.argmin_assign(np.zeros(3))


def test_model_bank_init_center0_exact(tiny):
    _, _, params = tiny
    bank = fl.ModelBank.init(params, 3, seed=0)
    assert bank.k == 3
    for a, b in zip(jax.tree.leaves(bank.center(0)),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # jittered centers differ from center 0 on inexact leaves
    assert _params_digest(bank.center(1)) != _params_digest(bank.center(0))
    # gather: row j is the assigned center
    g = bank.gather(np.asarray([2, 0, 1]))
    for leaf, s in zip(jax.tree.leaves(g), jax.tree.leaves(bank.stacked)):
        np.testing.assert_array_equal(np.asarray(leaf[1]),
                                      np.asarray(s[0]))


def test_registry_cluster_axis():
    assert "cluster" in fl.names.__globals__["KINDS"]
    assert sorted(fl.names("cluster")) == ["fesem", "ifca"]
    for comp in ("ifca", "ifca+maxent", "fesem"):
        recipe = fl.get("composition", comp)
        assert recipe.cluster in fl.names("cluster")
    assert fl.get("composition", "fedentropy").cluster is None


def test_perclstr_passthrough_without_cluster_key(tiny):
    """No ``cluster`` key in out -> the base weighted mean, exactly."""
    agg = fl.PerClusterAggregator()
    base = fl.WeightedAverageAggregator()
    rng = np.random.default_rng(0)
    out = {"params": {"w": jnp.asarray(rng.normal(size=(4, 3)))}}
    gp = {"w": jnp.asarray(rng.normal(size=(3,)))}
    sizes = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    np.testing.assert_array_equal(
        np.asarray(agg(gp, out, sizes, mask)["w"]),
        np.asarray(base(gp, out, sizes, mask)["w"]))


def test_perclstr_empty_cluster_keeps_center():
    """A cluster with no admitted member keeps its center unchanged."""
    agg = fl.PerClusterAggregator()
    rng = np.random.default_rng(1)
    stacked = {"w": jnp.asarray(rng.normal(size=(2, 3)))}
    out = {"params": {"w": jnp.asarray(rng.normal(size=(4, 3)))},
           "cluster": jnp.asarray([0, 0, 0, 0], jnp.int32)}
    sizes = jnp.asarray([1.0, 1.0, 1.0, 1.0])
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    new = agg(stacked, out, sizes, mask)
    # cluster 1 had no members at all: bitwise unchanged
    np.testing.assert_array_equal(np.asarray(new["w"][1]),
                                  np.asarray(stacked["w"][1]))
    # cluster 0 moved
    assert not np.array_equal(np.asarray(new["w"][0]),
                              np.asarray(stacked["w"][0]))
