"""Hypothesis properties of the async buffered engine (ISSUE satellites):

(a) staleness weights are monotone non-increasing in τ and reduce to
    uniform at α = 0;
(b) every admitted update appears in exactly one flush, over random
    seeds and clocks;
(c) the K=|cohort| zero-staleness reduction to the sequential ``Server``
    holds across seeds, not just the recorded seed.

Engine-level properties (b)/(c) train a real (tiny) CNN per example, so
example counts stay small; the deterministic fixed-seed twins live in
tests/test_async_engine.py and run everywhere hypothesis is absent
(locally the tier-1 suite skips this module; CI's dev extra installs
hypothesis and runs it, including on the forced 8-device mesh).
"""
import functools

import jax
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import repro.fl as fl  # noqa: E402
from repro.core.strategies import LocalSpec  # noqa: E402
from repro.data.partition import partition, stack_clients  # noqa: E402
from repro.data.synthetic import make_image_dataset  # noqa: E402
from repro.fl.runtime import AsyncConfig, staleness_weights  # noqa: E402
from repro.models import cnn  # noqa: E402


@functools.lru_cache(maxsize=1)
def _tiny():
    """Memoized module corpus (a plain function, not a pytest fixture, so
    @given draws never interact with fixture scoping)."""
    (xtr, ytr), _ = make_image_dataset(
        num_classes=4, train_per_class=60, test_per_class=15, hw=16,
        noise=0.4, seed=0)
    parts = partition("case1", ytr, 8, 4, seed=0)
    data = stack_clients(xtr, ytr, parts, batch_multiple=20)
    params = cnn.init(jax.random.PRNGKey(0), image_hw=16, num_classes=4)
    return data, params


def _build(seed, engine=None, runtime=None):
    data, params = _tiny()
    return fl.build("fedentropy", cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=seed),
                    LocalSpec(epochs=1, batch_size=20),
                    engine=engine, runtime=runtime)


# ------------------------------------------------- (a) staleness weights

@given(tau=st.lists(st.integers(min_value=0, max_value=50), min_size=1,
                    max_size=16),
       alpha=st.floats(min_value=0.0, max_value=8.0, allow_nan=False))
@settings(max_examples=200, deadline=None)
def test_staleness_weights_monotone_and_uniform_at_zero(tau, alpha):
    order = np.sort(np.asarray(tau))
    w = staleness_weights(order, alpha)
    assert np.all(w > 0) and np.all(w <= 1.0)
    assert np.all(np.diff(w) <= 0)               # monotone non-increasing
    np.testing.assert_allclose(staleness_weights(order, 0.0), 1.0)
    # strictly decreasing where tau strictly increases, for alpha large
    # enough that each step is representable in float64. The smallest
    # step here, tau 49 -> 50, scales a weight near 1 by ~1 - 0.02 alpha,
    # which float64 resolves only for alpha above ~1e-14 (at the 7.9e-97
    # Hypothesis finds, every weight rounds to 1.0); 1e-6 leaves margin
    if alpha >= 1e-6:
        inc = np.diff(order) > 0
        assert np.all(np.diff(w)[inc] < 0)


# ------------------------------------- (b) flushes partition the stream

@given(seed=st.integers(min_value=0, max_value=10_000),
       clock=st.sampled_from(["uniform", "straggler"]),
       buffer_size=st.sampled_from([0, 2, 3]))
@settings(max_examples=5, deadline=None)
def test_each_admitted_update_in_exactly_one_flush(seed, clock,
                                                   buffer_size):
    server = _build(seed=seed, engine="async", runtime=AsyncConfig(
        buffer_size=buffer_size, clock=clock, latency_scale=1.0,
        straggler_frac=0.25, straggler_factor=8.0, staleness_alpha=0.5,
        seed=seed))
    recs = [server.round() for _ in range(3)]
    seen: set = set()
    admitted_total = 0
    for rec in recs:
        batch = set(rec["seq"])
        assert len(batch) == len(rec["seq"])        # no double-screening
        assert not (batch & seen)                   # exactly-one-flush
        assert set(rec["admitted_seq"]) <= batch
        assert len(rec["admitted_seq"]) == len(rec["positive"])
        admitted_total += len(rec["admitted_seq"])
        seen |= batch
    assert admitted_total == sum(len(r["positive"]) for r in recs)


# ----------------------------------------- (c) reduction across seeds

@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=5, deadline=None)
def test_zero_staleness_reduction_across_seeds(seed):
    seq = _build(seed=seed)
    asy = _build(seed=seed, engine="async")
    for _ in range(2):
        a, b = seq.round(), asy.round()
        assert a["selected"] == b["selected"]
        assert a["positive"] == b["positive"]
        assert a["negative"] == b["negative"]
        assert a["comm"] == b["comm"]
        assert b["staleness"] == [0] * len(b["selected"])
    for x, y in zip(jax.tree.leaves(seq.global_params),
                    jax.tree.leaves(asy.global_params)):
        if len(jax.devices()) == 1:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6)
