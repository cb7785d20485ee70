"""The async buffered engine: reduction guarantee vs the sequential
``Server`` (golden AND live, bit-for-bit), straggler-clock determinism,
staleness damping, admission comm savings, the judge admission entry
points, and the engine/runtime registry error matrix."""
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
from repro.fl.judges import admit_candidates
from repro.fl.runtime import (
    ArrivalClock, AsyncBufferedServer, AsyncConfig, RuntimeConfig,
    staleness_weights,
)
from repro.models import cnn

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
SEQ_GOLDEN = os.path.join(GOLDEN_DIR, "seed_history.json")
ASYNC_GOLDEN = os.path.join(GOLDEN_DIR, "async_history.json")

# same tolerance policy as test_runtime_engine.py: ints exact everywhere,
# entropy floats exact on the single device the goldens were recorded on,
# tolerant under the forced multi-device CI mesh (different compiled
# program shapes perturb low float bits)
_SINGLE_DEVICE = len(jax.devices()) == 1
ENT_ATOL = 1e-9 if _SINGLE_DEVICE else 1e-6

_STRAGGLER = AsyncConfig(clock="straggler", latency_scale=1.0,
                         straggler_frac=0.25, straggler_factor=8.0,
                         staleness_alpha=0.5, seed=0)


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


def _build(tiny, name="fedentropy", runtime=None, engine="async",
           **overrides):
    data, params = tiny
    return fl.build(name, cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=0),
                    LocalSpec(epochs=1, batch_size=20),
                    engine=engine, runtime=runtime, **overrides)


def _params_digest(params) -> float:
    return float(sum(float(jnp.sum(jnp.abs(x)))
                     for x in jax.tree.leaves(params)))


def _assert_ints_match(rec, want):
    assert rec["selected"] == want["selected"]
    assert rec["positive"] == want["positive"]
    assert rec["negative"] == want["negative"]
    assert rec["comm"]["total_bytes"] == want["total_bytes"]
    ent = float(want["entropy"])
    if np.isnan(ent):
        assert np.isnan(rec["entropy"])
    else:
        assert rec["entropy"] == pytest.approx(ent, abs=ENT_ATOL)


# ------------------------------------------------------ reduction guarantee

@pytest.mark.parametrize("variant,comp", [("fedentropy", "fedentropy"),
                                          ("fedavg_uniform", "fedavg")])
def test_async_reduction_matches_sequential_golden(tiny, variant, comp):
    """ISSUE acceptance: K=|cohort| + zero-latency clock + damping off is
    bit-for-bit the sequential ``Server`` — checked against the SEQUENTIAL
    engine's own recorded golden, not an async-specific one."""
    with open(SEQ_GOLDEN) as f:
        golden = json.load(f)[variant]
    server = _build(tiny, comp, runtime=AsyncConfig())
    assert isinstance(server, AsyncBufferedServer)
    for _ in range(len(golden["history"])):
        rec = server.round()
        assert rec["staleness"] == [0] * len(rec["selected"])
        assert rec["flush_time"] == 0.0
    for rec, want in zip(server.history, golden["history"]):
        _assert_ints_match(rec, want)
    assert _params_digest(server.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=1e-7)


def test_async_reduction_matches_live_sequential(tiny, same_run):
    """Same reduction against a live sequential server in the same
    process: on one device every record key the sequential round writes
    and every params leaf are equal, bit for bit (same compiled program,
    same reduction), on any host."""
    data, params = tiny
    seq = fl.build("fedentropy", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20))
    asy = _build(tiny)
    for _ in range(4):
        seq.round()
        asy.round()
    if _SINGLE_DEVICE:
        same_run(asy, seq)
        return
    # the forced multi-device mesh shards the async fan-out: verdict ints
    # exact, floats to the multi-device tolerance
    for a, b in zip(seq.history, asy.history):
        for k in ("round", "selected", "positive", "negative"):
            assert a[k] == b[k]
        assert a["comm"] == b["comm"]
        assert b["entropy"] == pytest.approx(a["entropy"], abs=ENT_ATOL)
    for x, y in zip(jax.tree.leaves(seq.global_params),
                    jax.tree.leaves(asy.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# --------------------------------------------------------- straggler clock

def test_straggler_matches_async_golden(tiny):
    """The straggler-clock variant pins the async-specific record fields:
    virtual flush times, staleness distributions, arrival sequence ids."""
    with open(ASYNC_GOLDEN) as f:
        golden = json.load(f)["fedentropy_straggler"]
    server = _build(tiny, runtime=_STRAGGLER)
    for _ in range(len(golden["history"])):
        server.round()
    for rec, want in zip(server.history, golden["history"]):
        _assert_ints_match(rec, want)
        assert rec["staleness"] == want["staleness"]
        assert rec["seq"] == want["seq"]
        assert rec["admitted_seq"] == want["admitted_seq"]
        assert rec["flush_time"] == pytest.approx(
            float(want["flush_time"]), rel=1e-12)
    assert _params_digest(server.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=1e-6)
    # the heavy tail actually produced stale admissions
    assert any(max(r["staleness"]) > 0 for r in server.history)


def test_straggler_run_is_deterministic(tiny):
    """No wall-clock anywhere: two identical builds stream identically."""
    h1 = [_build(tiny, runtime=_STRAGGLER).round() for _ in range(1)]
    s2 = _build(tiny, runtime=_STRAGGLER)
    h2 = [s2.round()]
    for a, b in zip(h1, h2):
        assert a["selected"] == b["selected"]
        assert a["staleness"] == b["staleness"]
        assert a["flush_time"] == b["flush_time"]
        assert a["seq"] == b["seq"]


def test_flushes_partition_admitted_updates(tiny):
    """Every screened arrival lands in exactly one flush; admitted ids are
    a subset of the flush's arrivals (the deterministic twin of the
    hypothesis property in test_async_properties.py)."""
    server = _build(tiny, runtime=_STRAGGLER)
    recs = [server.round() for _ in range(4)]
    seen: set = set()
    for rec in recs:
        batch = set(rec["seq"])
        assert len(batch) == len(rec["seq"])       # no duplicate arrivals
        assert not (batch & seen)                  # disjoint across flushes
        assert set(rec["admitted_seq"]) <= batch
        assert len(rec["admitted_seq"]) == len(rec["positive"])
        assert len(rec["selected"]) >= server.buffer_size
        seen |= batch


class _StalenessAwareSelector(fl.PoolSelector):
    """A selector opting into the per-arrival staleness feed."""

    def __init__(self, num_clients, eps=0.8, seed=0):
        super().__init__(num_clients, eps, seed)
        self.seen: list = []

    def observe_staleness(self, arrivals):
        self.seen.append(arrivals)


def test_selector_staleness_feedback(tiny):
    """Selectors defining ``observe_staleness`` see every screened
    arrival's τ + verdict per flush; the round stream is untouched."""
    hook = _StalenessAwareSelector(8)
    server = _build(tiny, runtime=_STRAGGLER, selector=hook)
    plain = _build(tiny, runtime=_STRAGGLER)
    recs = [server.round() for _ in range(4)]
    for _ in range(4):
        plain.round()
    assert len(hook.seen) == len(recs)
    for batch, rec in zip(hook.seen, recs):
        assert [e["client"] for e in batch] == rec["selected"]
        admitted = [e["client"] for e in batch if e["admitted"]]
        assert sorted(admitted) == sorted(rec["positive"])
        assert all(isinstance(e["staleness"], int) and e["staleness"] >= 0
                   for e in batch)
    # pure observation: same stream as a hook-less run, bit-for-bit
    for a, b in zip(server.history, plain.history):
        assert a["selected"] == b["selected"]
        assert a["positive"] == b["positive"]
        assert a["entropy"] == b["entropy"]
    assert getattr(fl.PoolSelector(8), "observe_staleness", None) is None


def test_staleness_damping_changes_aggregation(tiny):
    """α > 0 dampens stale updates: same stream, different params."""
    damped = _build(tiny, runtime=_STRAGGLER)
    flat = _build(tiny, runtime=AsyncConfig(
        clock="straggler", latency_scale=1.0, straggler_frac=0.25,
        straggler_factor=8.0, staleness_alpha=0.0, seed=0))
    d0, f0 = damped.round(), flat.round()
    # flush 0 has zero staleness -> identical ints AND identical params
    assert d0["selected"] == f0["selected"]
    d1, f1 = damped.round(), flat.round()
    assert max(d1["staleness"]) > 0
    assert _params_digest(damped.global_params) != \
        _params_digest(flat.global_params)


def test_admission_saves_model_uplink_vs_fedavg(tiny):
    """ISSUE acceptance (test twin of BENCH_async.json): a straggler-clock
    async fedentropy run ships strictly fewer model bytes than
    round-synchronous fedavg at equal flush count."""
    data, params = tiny
    asy = _build(tiny, runtime=_STRAGGLER)
    favg = fl.build("fedavg", cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=0),
                    LocalSpec(epochs=1, batch_size=20))
    flushes = 4
    a_bytes = sum(asy.round()["comm"]["model_bytes"]
                  for _ in range(flushes))
    f_bytes = sum(favg.round()["comm"]["model_bytes"]
                  for _ in range(flushes))
    assert a_bytes < f_bytes


def test_buffer_size_knob(tiny):
    """Explicit K < |cohort| flushes early; the zero clock still screens
    whole simultaneous cohorts (tie overshoot), the straggler clock
    flushes at exactly K."""
    zero = _build(tiny, runtime=AsyncConfig(buffer_size=2))
    rec = zero.round()
    assert zero.buffer_size == 2
    assert len(rec["selected"]) == 4        # whole cohort ties at t=0
    strag = _build(tiny, runtime=AsyncConfig(
        buffer_size=2, clock="straggler", latency_scale=1.0,
        straggler_frac=0.25, straggler_factor=8.0, seed=0))
    rec = strag.round()
    assert len(rec["selected"]) == 2


def test_async_with_passthrough_judge(tiny):
    """judge="none" admits every arrival (NaN entropy) — the admission
    layer composes with any Judge via admit_candidates."""
    server = _build(tiny, "fedavg", runtime=_STRAGGLER)
    rec = server.round()
    assert rec["positive"] == rec["selected"] and rec["negative"] == []
    assert np.isnan(rec["entropy"])


# -------------------------------------------------- judge admission layer

def _skewed_soft(seed=0):
    """4 near-one-hot class signatures + sizes: class-0-heavy group."""
    rng = np.random.default_rng(seed)
    eye = np.eye(4)
    soft = 0.9 * eye[[0, 0, 0, 1]] + 0.1 * rng.dirichlet(np.ones(4), 4)
    return soft, np.full(4, 10.0)


def test_admit_empty_buffer_is_round_judgment():
    soft, sizes = _skewed_soft()
    judge = fl.MaxEntropyJudge()
    want = judge(soft, sizes)
    got = judge.admit(np.zeros((0, 4)), np.zeros((0,)), soft, sizes)
    assert got == want


def test_admit_protects_buffered_rows():
    """A buffer row the plain joint judgment would remove must stay: only
    candidates are admitted/rejected, and the rejection verdicts adapt to
    the protected group."""
    soft, sizes = _skewed_soft()
    judge = fl.MaxEntropyJudge()
    # plain joint judgment removes at least one class-0 row
    plain_a, plain_r, _ = judge(soft, sizes)
    assert plain_r
    # protect the two rows the plain sweep wanted gone -> as buffer they
    # cannot be rejected; verdicts only cover the 2 candidates
    buf = [plain_r[0], plain_a[0]]
    cand = [i for i in range(4) if i not in buf]
    a, r, ent = judge.admit(soft[buf], sizes[buf], soft[cand], sizes[cand])
    assert sorted(a + r) == [0, 1]          # candidate-relative, complete
    assert np.isfinite(ent)


def test_admit_backends_agree():
    soft, sizes = _skewed_soft()
    buf_soft, buf_sizes = soft[:2], sizes[:2]
    cand_soft, cand_sizes = soft[2:], sizes[2:]
    a_np, r_np, e_np = fl.MaxEntropyJudge("numpy").admit(
        buf_soft, buf_sizes, cand_soft, cand_sizes)
    for backend in ("xla", "pallas"):
        a, r, e = fl.MaxEntropyJudge(backend).admit(
            buf_soft, buf_sizes, cand_soft, cand_sizes)
        assert (a, r) == (a_np, r_np)
        assert e == pytest.approx(e_np, abs=1e-5)


def test_admit_candidates_fallback():
    soft, sizes = _skewed_soft()
    a, r, ent = admit_candidates(fl.PassThroughJudge(),
                                 soft[:2], sizes[:2], soft[2:], sizes[2:])
    assert a == [0, 1] and r == []
    assert np.isnan(ent)
    # relative-index mapping: a judge that rejects the last combined row
    a, r, _ = admit_candidates(fl.MaxEntropyJudge(),
                               np.zeros((0, 4)), np.zeros((0,)),
                               soft, sizes)
    assert sorted(a + r) == [0, 1, 2, 3]


def test_staleness_weights_shape_and_bounds():
    w = staleness_weights([0, 1, 3], 0.5)
    assert w[0] == 1.0 and np.all(np.diff(w) < 0)
    np.testing.assert_allclose(staleness_weights([0, 5, 9], 0.0), 1.0)
    with pytest.raises(ValueError, match=">= 0"):
        staleness_weights([-1], 0.5)


def test_arrival_clock_models():
    zero = ArrivalClock(AsyncConfig(), 8)
    assert np.all(zero.latency == 0.0)
    cfg = AsyncConfig(clock="straggler", latency_scale=2.0,
                      straggler_frac=0.25, straggler_factor=16.0, seed=3)
    clock = ArrivalClock(cfg, 8)
    again = ArrivalClock(cfg, 8)
    np.testing.assert_array_equal(clock.latency, again.latency)  # seeded
    assert np.sum(clock.latency > 2.0 * 1.5) == 2   # 25% of 8 straggle
    assert clock.arrival(0, 5.0) == 5.0 + clock.latency[0]


# ------------------------------------------------- registry error matrix

def test_engine_runtime_mismatches_error_loudly(tiny):
    with pytest.raises(ValueError, match="unknown engine 'warp'"):
        _build(tiny, engine="warp")
    with pytest.raises(ValueError, match="AsyncBufferedServer takes"):
        _build(tiny, engine="async", runtime=RuntimeConfig())
    with pytest.raises(ValueError, match="PipelinedServer takes"):
        _build(tiny, engine="pipelined", runtime=AsyncConfig())
    with pytest.raises(ValueError, match="SequentialEngine takes"):
        _build(tiny, engine="sequential", runtime=AsyncConfig())
    # direct construction is loud too, not just build()
    data, params = tiny
    with pytest.raises(ValueError, match="runtime=AsyncConfig"):
        AsyncBufferedServer(
            cnn.apply, params, data,
            fl.ServerConfig(num_clients=8, participation=0.5, seed=0),
            runtime=RuntimeConfig(),
            selector=fl.PoolSelector(8),
            strategy=fl.FedAvgStrategy(LocalSpec(epochs=1, batch_size=20)),
            judge=fl.MaxEntropyJudge(),
            aggregator=fl.WeightedAverageAggregator())


def test_async_config_routes_without_engine(tiny):
    server = _build(tiny, engine=None, runtime=AsyncConfig(buffer_size=3))
    assert isinstance(server, AsyncBufferedServer)
    assert server.buffer_size == 3
    assert fl.get("engine", "async") is AsyncBufferedServer


def test_async_refuses_group_strategies(tiny):
    with pytest.raises(ValueError, match="prepare_round"):
        _build(tiny, "fedcat+maxent")


def test_async_config_validation():
    for bad in (dict(clock="warp"), dict(buffer_size=-1),
                dict(staleness_alpha=-0.1), dict(latency_scale=-1.0),
                dict(straggler_frac=1.5), dict(straggler_factor=0.5),
                dict(concurrency=-2)):
        with pytest.raises(ValueError):
            AsyncConfig(**bad)
