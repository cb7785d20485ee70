"""The round-loop bugfix sweep: low-precision ``masked_mean_tree``
accumulation, the fused (M, P) aggregation path, ``BoundedJitCache``
build-outside-lock semantics, ``QueueSelector.stats`` queue_frac
reporting, the hoisted cohort sizing, and equal-instant async arrival
batching."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl as fl
from repro.core.aggregation import fused_aggregate, masked_mean_tree
from repro.core.strategies import LocalSpec
from repro.data.corpus import DataQueue
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.fl.runtime import AsyncConfig, ProcessCompileCache
from repro.fl.selectors import QueueSelector
from repro.fl.server import BoundedJitCache
from repro.models import cnn


# --------------------------------------- masked_mean_tree accumulation fix

def _ref_mean_f64(stacked, sizes, mask):
    """The float64 numpy oracle for the masked weighted mean."""
    w = np.asarray(sizes, np.float64) * np.asarray(mask, np.float64)
    tot = max(w.sum(), 1e-12)

    def leaf(x):
        x = np.asarray(x, np.float64)
        wl = w.reshape((-1,) + (1,) * (x.ndim - 1))
        return (x * wl).sum(axis=0) / tot

    return jax.tree.map(leaf, stacked)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_masked_mean_bf16_accumulates_in_f32(seed):
    """Summing a large cohort in bf16 (8 mantissa bits) loses mass; the
    fix accumulates in float32, so the result must sit within one bf16
    quantum of the float64 oracle for every seed."""
    rng = np.random.default_rng(seed)
    m = 64
    tree = {
        "w": jnp.asarray(rng.normal(size=(m, 37, 5)), jnp.bfloat16),
        "b": jnp.asarray(rng.normal(size=(m, 11)), jnp.bfloat16),
    }
    sizes = jnp.asarray(rng.integers(20, 200, size=m), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=m), jnp.float32)
    if float(jnp.sum(mask)) == 0:
        mask = mask.at[0].set(1.0)
    got = masked_mean_tree(tree, sizes, mask)
    want = _ref_mean_f64(tree, sizes, mask)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # one bf16 ulp (2^-8 relative) around the true mean — the old
        # bf16-accumulated sum drifted by many ulps at m=64
        err = np.abs(np.asarray(g, np.float64) - w)
        tol = np.maximum(np.abs(w), 1e-3) * 2.0 ** -8
        assert np.all(err <= tol)


def test_masked_mean_f32_bitwise_unchanged():
    """Float32 leaves must run the identical ops as before the fix —
    fixed-seed golden histories depend on it. ``masked_mean_tree`` is one
    compiled program, so the pre-fix formula is compiled too: the same
    ops in one program give the same bits."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(8, 13, 4)), jnp.float32)
    sizes = jnp.asarray(rng.integers(20, 200, size=8), jnp.float32)
    mask = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0], jnp.float32)
    got = masked_mean_tree({"x": x}, sizes, mask)["x"]

    @jax.jit
    def old(x, sizes, mask):
        # the pre-fix formula, verbatim: weights cast to the leaf dtype
        w = sizes * mask
        tot = jnp.clip(jnp.sum(w), 1e-12, None)
        return jnp.sum(x * w.reshape(-1, 1, 1).astype(x.dtype),
                       axis=0) / tot.astype(x.dtype)

    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(old(x, sizes, mask)))


# ----------------------------------------------------- fused aggregation

def _cnn_like(rng, m):
    return {
        "conv1": {"w": jnp.asarray(rng.normal(size=(m, 3, 3, 1, 8)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(m, 8)), jnp.float32)},
        "dense": {"w": jnp.asarray(rng.normal(size=(m, 128, 10)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(m, 10)), jnp.float32)},
    }


def _lm_like(rng, m):
    """Many small leaves + one embedding-shaped one, mixed dtypes."""
    tree = {"emb": jnp.asarray(rng.normal(size=(m, 96, 32)), jnp.float32)}
    for i in range(12):
        tree[f"blk{i}"] = {
            "attn": jnp.asarray(rng.normal(size=(m, 32, 32)), jnp.bfloat16),
            "ln": jnp.asarray(rng.normal(size=(m, 32)), jnp.float32),
        }
    return tree


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("treefn", [_cnn_like, _lm_like],
                         ids=["cnn", "lm"])
def test_fused_aggregate_matches_masked_mean(backend, treefn):
    """ISSUE acceptance: the one-launch flat segment-reduce matches the
    per-leaf tree_map mean to float32 tolerance on CNN and LM pytrees,
    on both the xla reference and the Pallas kernel."""
    rng = np.random.default_rng(42)
    m = 12
    tree = treefn(rng, m)
    sizes = jnp.asarray(rng.integers(20, 200, size=m), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=m), jnp.float32).at[0].set(1.)
    got = fused_aggregate(tree, sizes, mask, backend=backend)
    want = masked_mean_tree(tree, sizes, mask)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        assert g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g, np.float64), np.asarray(w, np.float64),
            rtol=1e-5, atol=1e-5)


def test_fused_aggregator_registered():
    agg = fl.get("aggregator", "fused")
    assert agg.from_config(config=None, local=None).backend is None


# ------------------------------------------- BoundedJitCache lock scope

def test_cache_build_does_not_block_other_keys():
    """A slow make() on one key must not stall lookups of other keys —
    the old implementation held the lock across make()."""
    cache = BoundedJitCache(maxsize=4)
    slow_started = threading.Event()
    slow_release = threading.Event()

    def slow_make():
        slow_started.set()
        assert slow_release.wait(timeout=10)
        return "slow"

    t = threading.Thread(target=cache.get, args=("slow", slow_make))
    t.start()
    assert slow_started.wait(timeout=10)
    # while "slow" is building, an unrelated key must go straight through
    done = []
    t2 = threading.Thread(
        target=lambda: done.append(cache.get("fast", lambda: "fast")))
    t2.start()
    t2.join(timeout=5)
    assert done == ["fast"], "unrelated get blocked behind a slow build"
    slow_release.set()
    t.join(timeout=10)
    assert cache.get("slow", lambda: "rebuilt") == "slow"


def test_cache_same_key_builds_once():
    """Concurrent misses on ONE key dedupe onto a single build; waiters
    adopt the builder's entry (1 miss + N-1 hits in the stats)."""
    cache = ProcessCompileCache(maxsize=4)
    calls = []
    gate = threading.Event()

    def make():
        calls.append(1)
        gate.wait(timeout=10)
        return object()

    results = [None] * 4

    def worker(i):
        results[i] = cache.get("k", make)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.2)       # let every thread reach the miss path
    gate.set()
    for t in threads:
        t.join(timeout=10)
    assert len(calls) == 1
    assert all(r is results[0] for r in results)
    assert cache.stats()["misses"] == 1
    assert cache.stats()["hits"] == 3


def test_cache_failed_build_recovers():
    """An exception inside make() must release the per-key claim so the
    next caller becomes the builder instead of deadlocking."""
    cache = BoundedJitCache(maxsize=4)
    with pytest.raises(RuntimeError, match="boom"):
        cache.get("k", lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    assert cache.get("k", lambda: "ok") == "ok"
    assert len(cache) == 1


# ------------------------------------------------ QueueSelector.stats fix

class _FakeCorpusStats:
    """Duck-typed stats surface QueueSelector.bind_data consumes."""

    def __init__(self, n):
        self._ent = np.linspace(1.0, 2.0, n)
        self._sizes = np.full(n, 100, np.int64)

    def label_entropy(self):
        return self._ent

    def sizes(self):
        return self._sizes


def test_queue_frac_reports_last_applied_schedule():
    """stats()["queue_frac"] is the schedule the LAST select applied:
    None before any select, frac(0) after the first, frac(1) after the
    second — never a peek at the upcoming round (the old
    ``frac(round_idx - 1)`` reported round 0's frac at construction)."""
    q = DataQueue(start_frac=0.25, rounds_to_full=4)
    sel = QueueSelector(8, eps=1.0, seed=0, queue=q)
    sel.bind_data(_FakeCorpusStats(8))
    assert sel.stats()["queue_frac"] is None
    sel.select(4)
    assert sel.stats()["queue_frac"] == pytest.approx(q.frac(0))
    sel.select(4)
    assert sel.stats()["queue_frac"] == pytest.approx(q.frac(1))
    assert q.frac(1) != q.frac(0)      # the two sides really differ


def test_queue_frac_stays_none_unbound():
    """Unbound (no corpus stats) the queue is off: select() must not
    fabricate a schedule fraction."""
    sel = QueueSelector(8, eps=1.0, seed=0)
    sel.select(4)
    assert sel.stats()["queue_frac"] is None


# ------------------------------------------------------- cohort sizing

@pytest.mark.parametrize("n,c,want", [
    (25, 0.1, 2),     # banker's rounding: round(2.5) == 2, not 3
    (35, 0.1, 4),     # round(3.5) == 4 — half-to-even both directions
    (8, 0.5, 4),
    (8, 0.01, 1),     # floor of 1
    (32, 0.156, 5),   # the paper's Table 1 setting
])
def test_cohort_size_half_to_even(n, c, want):
    cfg = fl.ServerConfig(num_clients=n, participation=c)
    assert cfg.cohort_size() == want


# -------------------------------------- async equal-instant arrival batch

@pytest.fixture(scope="module")
def tiny():
    (xtr, ytr), _ = make_image_dataset(
        num_classes=4, train_per_class=60, test_per_class=15, hw=16,
        noise=0.4, seed=0)
    parts = partition("case1", ytr, 8, 4, seed=0)
    data = stack_clients(xtr, ytr, parts, batch_multiple=20)
    params = cnn.init(jax.random.PRNGKey(0), image_hw=16, num_classes=4)
    return data, params


def _async(tiny, **cfg):
    data, params = tiny
    return fl.build("fedentropy", cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=0),
                    LocalSpec(epochs=1, batch_size=20),
                    engine="async", runtime=AsyncConfig(**cfg))


def test_equal_instant_arrivals_screen_as_one_batch(tiny):
    """Regression: every event sharing the next arrival instant pops as
    ONE batch, tie-broken by dispatch sequence — within a cohort (the
    zero-latency reduction) and across cohorts (concurrency > cohort
    puts two cohorts' arrivals at the same instant)."""
    # within one cohort: default concurrency == cohort size
    server = _async(tiny)
    server._ensure_inflight()
    batch = server._pop_batch()
    assert len(batch) == 4                       # the whole cohort at t=0
    assert [e["seq"] for e in batch] == sorted(e["seq"] for e in batch)
    assert not server._events

    # across cohorts: two cohorts in flight, all eight events at t=0
    server2 = _async(tiny, concurrency=8)
    server2._ensure_inflight()
    batch2 = server2._pop_batch()
    assert len(batch2) == 8
    seqs = [e["seq"] for e in batch2]
    assert seqs == sorted(seqs) == list(range(8))
    assert len({e["t_arr"] for e in batch2}) == 1
    assert not server2._events
