"""The ``repro.fl.runtime`` engines: golden-history equivalence of the
pipelined server (speculation off AND on), misspeculation fallback,
forced shard_map execution, the process-level compile cache, and the
engine registry plumbing."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl as fl
from repro.core.strategies import LocalSpec
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.fl.runtime import (
    RuntimeConfig, disable_process_cache, enable_process_cache,
    pad_to_multiple, process_cache,
)
from repro.models import cnn

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "seed_history.json")

# On a single device every engine compiles the identical program, so
# entropy is reproducible to the bit. Under a forced multi-device mesh
# (the XLA_FLAGS=--xla_force_host_platform_device_count CI job) the
# auto-sharded fan-out vmaps a different batch size than the recorder
# did, and CPU XLA is not bitwise-stable across batch sizes — verdict
# and selection ints stay exact, entropy floats carry a tolerance.
_SINGLE_DEVICE = len(jax.devices()) == 1
ENT_ATOL = 1e-9 if _SINGLE_DEVICE else 1e-6        # vs recorded goldens
ENT_ATOL_ENGINES = 1e-12 if _SINGLE_DEVICE else 1e-6   # engine vs engine


@pytest.fixture(scope="module")
def tiny():
    """Identical to the setup the golden histories were recorded with."""
    (xtr, ytr), _ = make_image_dataset(
        num_classes=4, train_per_class=60, test_per_class=15, hw=16,
        noise=0.4, seed=0)
    parts = partition("case1", ytr, 8, 4, seed=0)
    data = stack_clients(xtr, ytr, parts, batch_multiple=20)
    params = cnn.init(jax.random.PRNGKey(0), image_hw=16, num_classes=4)
    return data, params


def _params_digest(params) -> float:
    return float(sum(float(jnp.sum(jnp.abs(x)))
                     for x in jax.tree.leaves(params)))


def _build(tiny, name="fedentropy", runtime=None, engine="pipelined",
           **overrides):
    data, params = tiny
    return fl.build(name, cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=0),
                    LocalSpec(epochs=1, batch_size=20),
                    engine=engine, runtime=runtime, **overrides)


def _assert_matches_golden(history, golden):
    assert len(history) == len(golden)
    for g, w in zip(history, golden):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["comm"]["total_bytes"] == w["total_bytes"]
        ent = float(w["entropy"])
        if np.isnan(ent):
            assert np.isnan(g["entropy"])
        else:
            assert g["entropy"] == pytest.approx(ent, abs=ENT_ATOL)


# golden variant -> fl.build arguments (same mapping the legacy shim uses)
_VARIANTS = {
    "fedentropy": ("fedentropy", {}),
    "fedavg_uniform": ("fedavg", {}),
    "scaffold_fe": ("scaffold", {"selector": "pools", "judge": "maxent"}),
    "moon_nopools": ("moon", {"judge": "maxent"}),
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_pipelined_speculation_off_matches_golden(tiny, variant):
    """ISSUE acceptance: PipelinedServer (speculation disabled) reproduces
    the recorded seed histories bit-for-bit, params digest included."""
    with open(GOLDEN) as f:
        golden = json.load(f)[variant]
    name, overrides = _VARIANTS[variant]
    server = _build(tiny, name, **overrides)
    for _ in range(len(golden["history"])):
        server.round()
    _assert_matches_golden(server.history, golden["history"])
    assert _params_digest(server.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=1e-7)


def test_speculation_on_is_history_transparent(tiny):
    """With speculation ON the recorded history is still the oracle's,
    bit-for-bit vs golden — speculative draws happen on a throwaway
    selector copy adopted only when the device verdict is confirmed —
    and every record carries the spec_hit/redispatched flags."""
    with open(GOLDEN) as f:
        golden = json.load(f)["fedentropy"]
    server = _build(tiny, runtime=RuntimeConfig(speculate=True))
    for _ in range(len(golden["history"])):
        server.round()
    _assert_matches_golden(server.history, golden["history"])
    assert _params_digest(server.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=1e-7)
    for rec in server.history:
        assert isinstance(rec["spec_hit"], bool)
        assert isinstance(rec["redispatched"], bool)
    # the float32 device judge agrees with the oracle on this corpus
    assert all(r["spec_hit"] for r in server.history)


def test_speculation_pallas_backend(tiny):
    """spec_backend="pallas" routes speculation through the class-tiled
    entropy_judge_sweep kernel (interpret mode on CPU)."""
    with open(GOLDEN) as f:
        golden = json.load(f)["fedentropy"]
    server = _build(tiny, runtime=RuntimeConfig(speculate=True,
                                                spec_backend="pallas"))
    for _ in range(3):
        server.round()
    _assert_matches_golden(server.history, golden["history"][:3])


class _WrongSpeculationJudge(fl.MaxEntropyJudge):
    """Oracle = real maxent; traced form always admits everyone, so every
    round with a rejection misspeculates."""

    def traced(self):
        return fl.PassThroughJudge().traced()


def test_misspeculation_falls_back_and_stays_correct(tiny):
    """A wrong device verdict must be discarded: history and params still
    match golden, rounds after a miss are flagged redispatched."""
    with open(GOLDEN) as f:
        golden = json.load(f)["fedentropy"]
    server = _build(tiny, runtime=RuntimeConfig(speculate=True),
                    judge=_WrongSpeculationJudge())
    for _ in range(len(golden["history"])):
        server.round()
    _assert_matches_golden(server.history, golden["history"])
    assert _params_digest(server.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=1e-7)
    for prev, rec in zip(server.history, server.history[1:]):
        # golden rounds 0-2 reject a device -> speculation missed -> the
        # following round's compute was re-dispatched from the oracle
        assert rec["redispatched"] == (not prev["spec_hit"])
        assert prev["spec_hit"] == (not prev["negative"])


@pytest.mark.parametrize("case", ["spec-off", "spec-on", "spec-miss",
                                  "spec-miss-streaming"])
def test_pipelined_matches_live_server(tiny, case, same_run):
    """Same host, same process: the pipelined engine's history and params
    equal the sequential ``Server``'s bit for bit, with speculation off,
    on (every guess confirmed), and forced to miss on every round with a
    rejection — on the resident plane, and on the streaming plane, where
    the next cohort is prefetched and a miss cancels it. Unlike the
    recorded goldens this holds on any host."""
    axes = {}
    if case.startswith("spec-miss"):
        axes["judge"] = _WrongSpeculationJudge()
    if case.endswith("streaming"):
        axes["data_plane"] = "streaming"
    server = _build(tiny, engine=None, **axes)
    assert type(server) is fl.Server
    engine = _build(tiny, runtime=RuntimeConfig(
        speculate=case != "spec-off", shard=False), **axes)
    for _ in range(6):
        server.round()
        engine.round()
    same_run(engine, server)
    if case != "spec-off":
        hits = [r["spec_hit"] for r in engine.history]
        assert all(hits) if case == "spec-on" else not all(hits)
    if case.endswith("streaming"):
        assert engine.corpus.prefetch_stats()["cancelled"] > 0


def test_speculation_with_orderless_judge_keeps_pool_population(tiny):
    """Judges whose JudgmentResult has removal_order=None (budgeted) must
    still re-file rejected devices into the pools on a speculative hit —
    regression test for the pool-drain bug (rejects filed nowhere)."""
    server = _build(tiny, judge=fl.BudgetedJudge(budget=2),
                    runtime=RuntimeConfig(speculate=True))
    for _ in range(3):
        rec = server.round()
        assert len(rec["positive"]) == 2 and len(rec["negative"]) == 2
    stats = server.selector.stats()
    # every device not held by the pending speculative selection is
    # back in a pool: nothing leaked
    assert stats["positive"] + stats["negative"] == 8 - 4


def test_forced_shard_map_matches_sequential(tiny):
    """shard=True runs the shard_map fan-out even on the 1-device CPU mesh;
    verdicts and params must match the sequential server exactly."""
    data, params = tiny
    seq = fl.build("fedentropy", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20))
    sharded = _build(tiny, runtime=RuntimeConfig(shard=True))
    for _ in range(3):
        seq.round()
        sharded.round()
    for g, w in zip(sharded.history, seq.history):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["entropy"] == pytest.approx(w["entropy"],
                                             abs=ENT_ATOL_ENGINES)
    for a, b in zip(jax.tree.leaves(sharded.global_params),
                    jax.tree.leaves(seq.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_client_mesh_from_production_mesh(tiny):
    """A launch.mesh production-style mesh reduces to its client rows
    (one slot per ("pod","data") row) and drives a sharded round."""
    from repro.fl.runtime import CLIENT_AXIS, PipelinedServer, \
        client_mesh_from
    from repro.launch.mesh import fl_clients_for, make_host_mesh
    mesh = make_host_mesh()
    cm = client_mesh_from(mesh)
    assert dict(cm.shape) == {CLIENT_AXIS: fl_clients_for(mesh)}
    data, params = tiny
    server = PipelinedServer(
        cnn.apply, params, data,
        fl.ServerConfig(num_clients=8, participation=0.5, seed=0),
        selector=fl.PoolSelector(8), strategy=fl.FedAvgStrategy(
            LocalSpec(epochs=1, batch_size=20)),
        judge=fl.MaxEntropyJudge(), aggregator=fl.WeightedAverageAggregator(),
        runtime=RuntimeConfig(shard=True), mesh=mesh)
    rec = server.round()
    assert server.client_mesh().shape[CLIENT_AXIS] == fl_clients_for(mesh)
    assert len(rec["positive"]) + len(rec["negative"]) == 4


def test_pad_to_multiple():
    tree = {"x": jnp.arange(10).reshape(5, 2), "y": jnp.ones((5,))}
    padded = pad_to_multiple(tree, 4)
    assert padded["x"].shape == (8, 2) and padded["y"].shape == (8,)
    np.testing.assert_array_equal(np.asarray(padded["x"][:5]),
                                  np.arange(10).reshape(5, 2))
    np.testing.assert_array_equal(np.asarray(padded["x"][5:]),
                                  np.tile([[8, 9]], (3, 1)))
    same = pad_to_multiple(tree, 5)
    assert same["x"].shape == (5, 2)


# ------------------------------------------------ process compile cache

def test_process_cache_shares_compiles_across_servers(tiny):
    assert process_cache() is None        # default: per-server caches
    cache = enable_process_cache(maxsize=8)
    try:
        s1 = _build(tiny, engine=None)
        s2 = _build(tiny, engine=None)
        s1.round()
        assert cache.stats()["misses"] >= 1
        s2.round()
        assert cache.stats()["hits"] >= 1          # s2 reused s1's program
        assert len(s1._jit_cache) == 0             # per-server LRUs idle
        assert len(s2._jit_cache) == 0
    finally:
        disable_process_cache()
    assert process_cache() is None


def test_process_cache_rebound_trims():
    cache = enable_process_cache(maxsize=4)
    try:
        for i in range(4):
            cache.get(("k", i), lambda i=i: i)
        assert len(cache) == 4
        cache2 = enable_process_cache(maxsize=2)
        assert cache2 is cache and len(cache) == 2
    finally:
        disable_process_cache()


# ------------------------------------------------------ registry plumbing

def test_engine_registry(tiny):
    from repro.fl.runtime import PipelinedServer, SequentialEngine
    assert fl.get("engine", "pipelined") is PipelinedServer
    assert fl.get("engine", "sequential") is SequentialEngine
    # unknown engine names fail in build() with the registered names listed
    # (not a KeyError deep in construction) — see tests/test_async_engine.py
    # for the engine/runtime mismatch matrix
    with pytest.raises(ValueError, match="unknown engine 'warp'.*async.*"
                                         "pipelined.*sequential"):
        _build(tiny, engine="warp")
    assert isinstance(_build(tiny), PipelinedServer)
    assert isinstance(_build(tiny, engine=None), fl.Server)
    # a RuntimeConfig without an engine routes to the engine it configures
    # rather than being silently ignored by the sequential driver
    s = _build(tiny, engine=None, runtime=RuntimeConfig(speculate=True))
    assert isinstance(s, PipelinedServer)
    assert s.runtime.speculate
    s2 = _build(tiny, engine="sequential", runtime=RuntimeConfig())
    assert isinstance(s2, SequentialEngine)


# -------------------------------------------- launch satellite: dryrun fix

def test_cost_analysis_dict_shapes():
    """jax 0.4.3x returns a per-device LIST from cost_analysis(); older
    stacks one dict; both (and None) must normalize."""
    from repro.launch.hlo_analysis import cost_analysis_dict
    assert cost_analysis_dict(None) == {}
    assert cost_analysis_dict([]) == {}
    assert cost_analysis_dict({"flops": 1.0}) == {"flops": 1.0}
    assert cost_analysis_dict([{"flops": 2.0}, {"flops": 2.0}]) == \
        {"flops": 2.0}
    got = cost_analysis_dict(jax.jit(lambda x: x * 2).lower(
        jnp.ones((4,))).compile().cost_analysis())
    assert isinstance(got, dict)
