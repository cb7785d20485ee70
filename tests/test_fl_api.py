"""The pluggable ``repro.fl`` server API: registry round-trips, legacy-shim
equivalence (bit-for-bit vs recorded seed-trainer histories), custom
components, and the bounded per-server jit cache."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl as fl
from repro.core.simulator import FedEntropyTrainer, FLConfig
from repro.core.strategies import LocalSpec
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.models import cnn

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "seed_history.json")


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


# ---------------------------------------------------------------- registry

def test_registry_roundtrip():
    assert fl.get("judge", "maxent") is fl.MaxEntropyJudge
    assert fl.get("selector", "pools") is fl.PoolSelector
    assert "fedentropy" in fl.names("composition")
    for comp in fl.names("composition"):
        recipe = fl.get("composition", comp)
        # every axis the recipe names must itself resolve
        fl.get("strategy", recipe.strategy)
        fl.get("selector", recipe.selector)
        fl.get("judge", recipe.judge)
        fl.get("aggregator", recipe.aggregator)
        if recipe.cluster is not None:
            assert fl.get("cluster", recipe.cluster) is not None
    # the cluster axis registers like any other kind
    assert fl.get("cluster", "ifca") is fl.IFCAAssigner
    assert fl.get("cluster", "fesem") is fl.FeSEMAssigner
    assert fl.get("composition", "ifca+maxent").cluster == "ifca"


def test_registry_unknown_name_errors():
    with pytest.raises(KeyError, match="no judge registered under 'nope'"):
        fl.get("judge", "nope")
    with pytest.raises(ValueError, match="unknown kind"):
        fl.register("flavor", "vanilla", object())


def test_register_and_build_custom_judge(tiny):
    """A user-defined Judge plugs through the registry by name."""
    data, params = tiny
    calls = []

    @fl.register("judge", "keep-first-two")
    class KeepFirstTwo:
        def __call__(self, soft_labels, sizes):
            calls.append(len(sizes))
            keep = list(range(min(2, len(sizes))))
            drop = list(range(2, len(sizes)))
            return keep, drop, 0.0

    server = fl.build("fedavg", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=0.5),
                      LocalSpec(epochs=1, batch_size=20),
                      judge="keep-first-two")
    rec = server.round()
    assert calls == [4]
    assert len(rec["positive"]) == 2 and len(rec["negative"]) == 2


def test_build_runs_fedentropy_and_fedavg(tiny):
    data, params = tiny
    for name in ("fedentropy", "fedavg"):
        server = fl.build(name, cnn.apply, params, data,
                          fl.ServerConfig(num_clients=8, participation=0.5),
                          LocalSpec(epochs=1, batch_size=20))
        rec = server.round()
        assert len(rec["selected"]) == 4
        assert len(rec["positive"]) + len(rec["negative"]) == 4
    # fedavg composition admits everyone (PassThroughJudge)
    assert not rec["negative"]


class _HostReadingAggregator:
    """A host-side wrapper that reads its inputs back with ``np.asarray``,
    as the benchmark's recording wrapper does. It could not run under a
    jit placed around the server's aggregator call."""

    def __init__(self, inner):
        self.inner = inner
        self.seen = []

    def __call__(self, global_params, out, sizes, mask):
        self.seen.append((
            [np.asarray(x) for x in jax.tree.leaves(global_params)],
            [np.asarray(x) for x in jax.tree.leaves(out["params"])],
            np.asarray(sizes), np.asarray(mask)))
        return self.inner(global_params, out, sizes, mask)


def test_host_wrapper_around_aggregator_keeps_history(tiny):
    """The aggregator's jit lives inside it, so wrapping
    ``server.aggregator`` in host code still runs ``Server.round()`` and
    gives the same history and model as the bare aggregator."""
    data, params = tiny

    def build():
        return fl.build("fedentropy", cnn.apply, params, data,
                        fl.ServerConfig(num_clients=8, participation=0.5,
                                        seed=0),
                        LocalSpec(epochs=1, batch_size=20))

    bare, wrapped = build(), build()
    wrapped.aggregator = _HostReadingAggregator(wrapped.aggregator)
    for _ in range(3):
        bare.round()
        wrapped.round()
    assert len(wrapped.aggregator.seen) == 3
    for g, w in zip(wrapped.history, bare.history):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["entropy"] == pytest.approx(w["entropy"], nan_ok=True)
    for a, b in zip(jax.tree.leaves(wrapped.global_params),
                    jax.tree.leaves(bare.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- shim equivalence

_VARIANTS = {
    "fedentropy": ("fedavg", True, True),
    "fedavg_uniform": ("fedavg", False, False),
    "scaffold_fe": ("scaffold", True, True),
    "moon_nopools": ("moon", True, False),
}


def _histories_equal(got: list, want: list):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["comm"]["total_bytes"] == w["total_bytes"]
        ent = float(w["entropy"])
        if np.isnan(ent):
            assert np.isnan(g["entropy"])
        else:
            assert g["entropy"] == pytest.approx(ent, abs=1e-9)


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_shim_reproduces_seed_histories_bitforbit(tiny, variant):
    """The refactored trainer must match histories recorded from the
    pre-refactor monolithic simulator on the same fixed seeds."""
    data, params = tiny
    with open(GOLDEN) as f:
        golden = json.load(f)[variant]
    strat, use_judgment, use_pools = _VARIANTS[variant]
    tr = FedEntropyTrainer(
        cnn.apply, params, data,
        FLConfig(num_clients=8, participation=0.5,
                 use_judgment=use_judgment, use_pools=use_pools, seed=0),
        LocalSpec(strategy=strat, epochs=1, batch_size=20))
    for _ in range(len(golden["history"])):
        tr.round()
    _histories_equal(tr.history, golden["history"])
    assert _params_digest(tr.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=1e-7)


def test_shim_equals_server_over_rounds(tiny):
    """FedEntropyTrainer and an explicitly-composed repro.fl.Server produce
    identical history (selected/positive/negative/entropy/comm) and params
    over several rounds on a fixed seed."""
    data, params = tiny
    tr = FedEntropyTrainer(
        cnn.apply, params, data,
        FLConfig(num_clients=8, participation=0.5, seed=0),
        LocalSpec(epochs=1, batch_size=20))
    server = fl.build("fedentropy", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=0.5,
                                      seed=0),
                      LocalSpec(epochs=1, batch_size=20))
    for _ in range(4):
        tr.round()
        server.round()
    for g, w in zip(tr.history, server.history):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["entropy"] == pytest.approx(w["entropy"], nan_ok=True)
        assert g["comm"] == w["comm"]
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(tr.global_params)[0]),
        np.asarray(jax.tree.leaves(server.global_params)[0]))


def test_shim_uniform_ablation_updates_shadow_pools(tiny):
    data, params = tiny
    tr = FedEntropyTrainer(
        cnn.apply, params, data,
        FLConfig(num_clients=8, participation=0.5, use_pools=False, seed=0),
        LocalSpec(epochs=1, batch_size=20))
    rec = tr.round()
    stats = tr.pools.stats()          # legacy observable, still maintained
    # legacy semantics: no select() ran on these pools, so positives stay
    # full and judged negatives accumulate alongside
    assert stats["positive"] == 8
    assert stats["negative"] == len(rec["negative"])


def test_conflicting_localspec_strategy_rejected(tiny):
    """A LocalSpec naming a different update rule than the composition is
    an error, not a silent override."""
    data, params = tiny
    with pytest.raises(ValueError, match="conflicts with the 'fedavg'"):
        fl.build("fedentropy", cnn.apply, params, data,
                 fl.ServerConfig(num_clients=8, participation=0.5),
                 LocalSpec(strategy="scaffold"))
    # the matching name (or the default) is fine
    fl.build("scaffold", cnn.apply, params, data,
             fl.ServerConfig(num_clients=8, participation=0.5),
             LocalSpec(strategy="scaffold"))


# ------------------------------------------------- strategy state pytrees

def test_strategy_state_is_explicit_pytree(tiny):
    data, params = tiny
    server = fl.build("scaffold", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=0.5),
                      LocalSpec(strategy="scaffold", epochs=1,
                                batch_size=20))
    assert set(server.state) == {"c_global", "c_local"}
    before = jax.tree.map(lambda x: x.copy(), server.state["c_global"])
    server.round()
    moved = any(float(jnp.abs(a - b).max()) > 0 for a, b in zip(
        jax.tree.leaves(before), jax.tree.leaves(server.state["c_global"])))
    assert moved


# ------------------------------------------------------ bounded jit cache

def test_bounded_jit_cache_evicts_lru():
    cache = fl.BoundedJitCache(2)
    makes = []
    for key in ("a", "b", "a", "c", "b"):
        cache.get(key, lambda k=key: makes.append(k) or k)
    # "a" was refreshed before "c" evicted "b"; re-getting "b" recompiles
    assert makes == ["a", "b", "c", "b"]
    assert len(cache) == 2


def test_server_owns_its_cache(tiny):
    data, params = tiny
    cfg = fl.ServerConfig(num_clients=8, participation=0.5, jit_cache_size=2)
    s1 = fl.build("fedavg", cnn.apply, params, data, cfg,
                  LocalSpec(epochs=1, batch_size=20))
    s2 = fl.build("fedavg", cnn.apply, params, data, cfg,
                  LocalSpec(epochs=1, batch_size=20))
    s1.round()
    assert len(s1._jit_cache) == 1 and len(s2._jit_cache) == 0


# -------------------------------------------- selector / eval edge guards

def test_pool_selector_clamps_oversized_draw(tiny):
    """participation * num_clients > num_clients must clamp to the
    population (like UniformSelector/QueueSelector), not over-draw."""
    sel = fl.PoolSelector(8)
    got = sel.select(12)
    assert sorted(got) == list(range(8))          # everyone, exactly once
    # end to end: an oversaturated config still runs a full round
    data, params = tiny
    server = fl.build("fedentropy", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=1.5,
                                      seed=0),
                      LocalSpec(epochs=1, batch_size=20))
    rec = server.round()
    assert sorted(rec["selected"]) == list(range(8))
    assert len(rec["positive"]) + len(rec["negative"]) == 8


def test_evaluate_empty_eval_set_fails_loudly(tiny):
    """n=0 raises a clear ValueError instead of dying in range(0, 0, 0)."""
    data, params = tiny
    server = fl.build("fedavg", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=0.5),
                      LocalSpec(epochs=1, batch_size=20))
    x = jnp.zeros((0, 16, 16, 3), jnp.float32)
    y = jnp.zeros((0,), jnp.int32)
    with pytest.raises(ValueError, match="empty eval set"):
        server.evaluate(x, y)
