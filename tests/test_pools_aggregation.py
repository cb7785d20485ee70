"""Device pools (Alg. 2 l.4-8/22) and weighted aggregation (l.21)."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import aggregate, comm_bytes
from repro.core.pools import DevicePools
from repro.fl.aggregators import (DeviceConcatAggregator,
                                  FusedAverageAggregator,
                                  PerClusterAggregator, ScaffoldAggregator,
                                  WeightedAverageAggregator)
from repro.models import cnn


def test_pools_start_all_positive():
    p = DevicePools(20)
    assert p.stats() == {"positive": 20, "negative": 0}


def test_select_removes_and_update_refiles():
    p = DevicePools(10, seed=0)
    sel = p.select(4)
    assert len(sel) == 4
    assert p.stats()["positive"] == 6
    p.update(sel[:1], sel[1:])
    assert p.stats() == {"positive": 7, "negative": 3}
    assert set(sel[1:]) <= p.negative


def test_select_overflows_to_other_pool():
    p = DevicePools(10, eps=0.0, seed=1)   # always try negative pool first
    sel = p.select(5)                       # negative pool empty -> positive
    assert len(sel) == 5


def test_eps_greedy_distribution():
    """With eps=0.8 the positive pool is preferred ~80% of the time."""
    hits = 0
    trials = 300
    for seed in range(trials):
        p = DevicePools(10, eps=0.8, seed=seed)
        p.positive = set(range(5))
        p.negative = set(range(5, 10))
        sel = p.select(2)
        if set(sel) <= set(range(5)):
            hits += 1
    assert 0.7 < hits / trials < 0.9


def test_aggregate_matches_paper_formula(rng):
    m = 5
    stacked = {"w": jnp.asarray(rng.normal(size=(m, 3, 4)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(m, 4)), jnp.float32)}
    sizes = jnp.asarray([10, 20, 30, 40, 50], jnp.float32)
    mask = jnp.asarray([1, 0, 1, 0, 1], jnp.float32)
    agg = aggregate(stacked, sizes, mask)
    w = np.asarray(sizes) * np.asarray(mask)
    ref = (np.asarray(stacked["w"]) * w[:, None, None]).sum(0) / w.sum()
    np.testing.assert_allclose(np.asarray(agg["w"]), ref, rtol=1e-5)


def test_aggregate_all_positive_is_weighted_fedavg(rng):
    m = 4
    stacked = {"w": jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)}
    sizes = jnp.ones((m,), jnp.float32)
    agg = aggregate(stacked, sizes, jnp.ones((m,)))
    np.testing.assert_allclose(np.asarray(agg["w"]),
                               np.asarray(stacked["w"]).mean(0), rtol=1e-5)


def test_comm_bytes_savings():
    """Dropping negatives must save bytes; soft labels are tiny."""
    tmpl = {"w": jnp.zeros((1000, 1000), jnp.float32)}   # 4 MB model
    full = comm_bytes(tmpl, num_selected=10, num_positive=10,
                      num_classes=10)
    half = comm_bytes(tmpl, num_selected=10, num_positive=5,
                      num_classes=10)
    assert half["total_bytes"] < full["total_bytes"]
    assert half["savings_fraction"] == pytest.approx(0.5, abs=0.01)
    assert full["soft_label_bytes"] < 0.001 * full["model_bytes"]


def test_comm_bytes_scaffold_doubles():
    tmpl = {"w": jnp.zeros((100, 100), jnp.float32)}
    a = comm_bytes(tmpl, 10, 10, 10, control_variate=False)
    b = comm_bytes(tmpl, 10, 10, 10, control_variate=True)
    assert b["model_bytes"] == 2 * a["model_bytes"]


_M = 6
_AGGREGATORS = {
    "weighted": (WeightedAverageAggregator, None),
    "fused": (lambda: FusedAverageAggregator(backend="xla"), None),
    "scaffold": (lambda: ScaffoldAggregator(lr_g=0.5), None),
    # three chains, the second broken at its second stage
    "devconcat-chain": (DeviceConcatAggregator,
                        {"group_id": [0, 0, 0, 1, 1, 2],
                         "chain_pos": [0, 1, 2, 0, 1, 0]}),
    "devconcat-plain": (DeviceConcatAggregator, None),
    # K=3 centers, cluster 1 has no member
    "perclstr": (PerClusterAggregator, {"cluster": [0, 0, 2, 2, 0, 2]}),
}


def _cohort(rng, dtype, k=None):
    lead = () if k is None else (k,)

    def tree(n):
        return {"w": jnp.asarray(rng.normal(size=n + (3, 4)), dtype),
                "b": jnp.asarray(rng.normal(size=n + (4,)), dtype)}

    return tree(lead), tree((_M,))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(_AGGREGATORS))
def test_compiled_aggregator_matches_eager_formula(rng, case, dtype):
    """Each aggregator's one compiled program computes what its formula
    gives evaluated op by op without jit, and keeps every leaf's dtype."""
    make, extra = _AGGREGATORS[case]
    agg = make()
    k = 3 if case == "perclstr" else None
    global_params, stacked = _cohort(rng, dtype, k)
    out = {"params": stacked}
    for key, val in (extra or {}).items():
        out[key] = jnp.asarray(val, jnp.int32)
    sizes = jnp.asarray([10, 20, 30, 40, 50, 60], jnp.float32)
    mask = jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32)
    got = agg(global_params, out, sizes, mask)
    with jax.disable_jit():
        want = agg(global_params, out, sizes, mask)
    assert jax.tree.structure(got) == jax.tree.structure(global_params)
    for g, w, ref in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                         jax.tree.leaves(global_params)):
        assert g.dtype == ref.dtype and g.shape == ref.shape
        g32, w32 = (np.asarray(x, np.float32) for x in (g, w))
        if dtype == jnp.float32:
            np.testing.assert_allclose(g32, w32, rtol=1e-6, atol=1e-7)
        else:   # f32 accumulation both ways: at most one bf16 rounding
            np.testing.assert_allclose(g32, w32, rtol=2.0 ** -7,
                                       atol=2.0 ** -7)


def test_weighted_aggregator_is_one_compiled_program(caplog):
    """On the paper CNN (10 leaves) stacked x10, a first call compiles
    exactly one program and a second compiles none: no per-leaf eager
    launches."""
    params = cnn.init(jax.random.PRNGKey(0))
    assert len(jax.tree.leaves(params)) == 10
    stacked = jax.tree.map(
        lambda x: jnp.stack([x + 0.01 * i for i in range(10)]), params)
    sizes = jnp.arange(1.0, 11.0, dtype=jnp.float32)
    mask = jnp.asarray([1.0, 0.0] * 5, jnp.float32)
    jax.block_until_ready((stacked, sizes, mask))
    agg = WeightedAverageAggregator()
    jax.clear_caches()

    def compiles():
        return [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("Compiling ")]

    caplog.set_level(logging.WARNING)
    with jax.log_compiles():
        jax.block_until_ready(agg(params, {"params": stacked}, sizes, mask))
        first = compiles()
        caplog.clear()
        jax.block_until_ready(agg(params, {"params": stacked}, sizes, mask))
        second = compiles()
    assert len(first) == 1 and first[0].startswith("Compiling jit(aggregate)")
    assert second == []
