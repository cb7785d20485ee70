"""The R-round ``lax.scan`` engine: golden and live-sequential history
equivalence for R in {1, 4}, forced-misspeculation truncation + oracle
replay, eligibility fallback for stateful selectors, device-mode
selection determinism, block-granular parameter semantics, and the
engine/runtime registry error matrix."""
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl as fl
from repro.core.judgment import JudgmentResult
from repro.core.strategies import LocalSpec
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.fl.runtime import RuntimeConfig, ScanConfig, ScanServer
from repro.models import cnn

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "seed_history.json")

# same tolerance policy as test_runtime_engine.py: selection/verdict ints
# exact everywhere, entropy floats exact on the single device the goldens
# were recorded on, tolerant under the forced multi-device CI mesh
_SINGLE_DEVICE = len(jax.devices()) == 1
ENT_ATOL = 1e-9 if _SINGLE_DEVICE else 1e-6
DIGEST_REL = 1e-7 if _SINGLE_DEVICE else 1e-5


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


def _build(tiny, name="fedavg", runtime=None, engine="scan", **overrides):
    data, params = tiny
    return fl.build(name, cnn.apply, params, data,
                    fl.ServerConfig(num_clients=8, participation=0.5,
                                    seed=0),
                    LocalSpec(epochs=1, batch_size=20),
                    engine=engine, runtime=runtime, **overrides)


def _params_digest(params) -> float:
    return float(sum(float(jnp.sum(jnp.abs(x)))
                     for x in jax.tree.leaves(params)))


def _assert_records_equal(got, want):
    """Live engine-vs-engine comparison: everything int exact, entropy to
    the device tolerance."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for k in ("round", "selected", "positive", "negative"):
            assert g[k] == w[k]
        assert g["comm"] == w["comm"]
        ent = float(w["entropy"])
        if np.isnan(ent):
            assert np.isnan(g["entropy"])
        else:
            assert g["entropy"] == pytest.approx(ent, abs=ENT_ATOL)


# ----------------------------------------------------- golden equivalence

@pytest.mark.parametrize("R", [1, 4])
def test_scan_matches_golden_fedavg(tiny, R):
    """ISSUE acceptance: ScanServer histories are bit-for-bit the
    sequential ``Server``'s on the golden seed for R in {1, 4}."""
    with open(GOLDEN) as f:
        golden = json.load(f)["fedavg_uniform"]
    server = _build(tiny, runtime=ScanConfig(rounds_per_scan=R))
    assert isinstance(server, ScanServer)
    assert server.scan_rounds() == R
    n = len(golden["history"])
    for _ in range(n):
        server.round()
    assert len(server.history) == n
    for g, w in zip(server.history, golden["history"]):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        assert g["comm"]["total_bytes"] == w["total_bytes"]
        ent = float(w["entropy"])
        if np.isnan(ent):
            assert np.isnan(g["entropy"])
        else:
            assert g["entropy"] == pytest.approx(ent, abs=ENT_ATOL)
        if R > 1:     # R=1 is the plain sequential round: no spec flags
            assert g["spec_hit"] is True
    if R == 1:
        # params advance block-at-a-time; only the R=1 run is at the
        # same round the golden digest was recorded at (R=4 has already
        # computed rounds 5..7 of the second block)
        assert _params_digest(server.global_params) == pytest.approx(
            float(golden["params_digest"]), rel=DIGEST_REL)


def test_scan_matches_live_sequential_fedentropy(tiny):
    """fedentropy with the Fig. 3b uniform selector (judgment, no pools):
    8 rounds = two full R=4 blocks against a live sequential Server —
    histories equal and end-of-block params equal."""
    data, params = tiny
    seq = fl.build("fedentropy", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20),
                   selector="uniform")
    scan = _build(tiny, "fedentropy", runtime=ScanConfig(rounds_per_scan=4),
                  selector="uniform")
    assert scan.scan_rounds() == 4
    for _ in range(8):
        seq.round()
        scan.round()
    _assert_records_equal(scan.history, seq.history)
    assert all(r["spec_hit"] for r in scan.history)
    assert _params_digest(scan.global_params) == pytest.approx(
        _params_digest(seq.global_params), rel=DIGEST_REL)


def test_scan_pallas_judge_backend(tiny):
    """spec_backend="pallas" speculates in-scan through the class-tiled
    entropy_judge_sweep kernel (interpret mode on CPU)."""
    with open(GOLDEN) as f:
        golden = json.load(f)["fedavg_uniform"]
    server = _build(tiny, runtime=ScanConfig(rounds_per_scan=4,
                                             spec_backend="pallas"))
    for _ in range(4):
        server.round()
    for g, w in zip(server.history, golden["history"][:4]):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]


# -------------------------------------------------- misspeculation replay

class _WrongScanJudge(fl.MaxEntropyJudge):
    """Oracle = real maxent; traced form always admits everyone, so any
    round with a rejection misspeculates and must truncate the block."""

    def traced(self):
        return fl.PassThroughJudge().traced()


def test_scan_forced_mismatch_truncates_and_replays(tiny):
    """A wrong in-scan verdict must be discarded: the mismatched round
    re-runs eagerly from the float64 oracle, the remaining pre-drawn
    cohorts re-scan, and the recorded history still equals the sequential
    Server's (whose oracle is the same maxent judge) bit-for-bit."""
    data, params = tiny
    seq = fl.build("fedentropy", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20),
                   selector="uniform")
    scan = _build(tiny, "fedentropy", runtime=ScanConfig(rounds_per_scan=4),
                  selector="uniform", judge=_WrongScanJudge())
    for _ in range(8):
        seq.round()
        scan.round()
    _assert_records_equal(scan.history, seq.history)
    assert _params_digest(scan.global_params) == pytest.approx(
        _params_digest(seq.global_params), rel=DIGEST_REL)
    # the sequential run rejects someone in these 8 rounds, so at least
    # one scan round misspeculated (spec_hit=False) and at least one
    # later confirmed round came from a truncated re-scan (redispatched)
    assert any(r["negative"] for r in seq.history)
    assert any(not r["spec_hit"] for r in scan.history)
    assert any(r["redispatched"] for r in scan.history)
    for r in scan.history:
        if not r["spec_hit"]:
            assert r["negative"], "only rejection rounds can misspeculate"


class _RejectAllScanJudge(fl.MaxEntropyJudge):
    """Oracle = real maxent; the traced form rejects everyone, so every
    in-scan round misspeculates and the block replays it off the oracle."""

    def traced(self):
        def none_in(soft, sizes):
            m = soft.shape[0]
            nan = jnp.full((), jnp.nan, jnp.float32)
            return JudgmentResult(
                mask=jnp.zeros((m,), jnp.float32), entropy=nan,
                initial_entropy=nan,
                num_removed=jnp.full((), m, jnp.int32),
                removal_order=jnp.arange(m, dtype=jnp.int32))
        return none_in


@pytest.mark.parametrize("mode", ["stack", "remat"])
def test_scan_oracle_replay_matches_live_server(tiny, mode, same_run):
    """Same host, same process: a scan engine whose every round falls
    back to its oracle replay equals the sequential ``Server`` bit for
    bit — records and every params leaf."""
    data, params = tiny
    server = fl.build("fedentropy", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=0.5,
                                      seed=0),
                      LocalSpec(epochs=1, batch_size=20),
                      selector="uniform")
    engine = _build(tiny, "fedentropy",
                    runtime=ScanConfig(rounds_per_scan=4, params_mode=mode,
                                       shard=False),
                    selector="uniform", judge=_RejectAllScanJudge())
    assert engine.scan_rounds() == 4
    for _ in range(4):
        server.round()
        engine.round()
    same_run(engine, server)
    assert not any(r["spec_hit"] for r in engine.history)


# ------------------------------------------------- remat memory mode

@pytest.mark.parametrize("R", [1, 4])
def test_scan_remat_matches_stack_and_sequential(tiny, R):
    """ISSUE acceptance: params_mode="remat" histories are bit-for-bit
    both params_mode="stack" and the sequential Server under a
    forced-mismatch judge — the rematerialized rewind point must be the
    exact params the stacked ys would have held."""
    data, params = tiny
    seq = fl.build("fedentropy", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20),
                   selector="uniform")
    engines = {}
    for mode in ("stack", "remat"):
        engines[mode] = _build(
            tiny, "fedentropy",
            runtime=ScanConfig(rounds_per_scan=R, params_mode=mode),
            selector="uniform", judge=_WrongScanJudge())
        assert engines[mode].scan_rounds() == R
    for _ in range(8):
        seq.round()
        for s in engines.values():
            s.round()
    for s in engines.values():
        _assert_records_equal(s.history, seq.history)
    if R > 1:
        # the forced-mismatch judge really exercised the rewind path
        assert any(not r["spec_hit"] for r in engines["remat"].history)
    # stack and remat must agree bitwise, not merely to tolerance
    for a, b in zip(jax.tree.leaves(engines["stack"].global_params),
                    jax.tree.leaves(engines["remat"].global_params)):
        assert bool(jnp.all(a == b))
    assert _params_digest(engines["remat"].global_params) == pytest.approx(
        _params_digest(seq.global_params), rel=DIGEST_REL)


def test_scan_remat_ys_carry_no_params(tiny):
    """The remat block's stacked ys hold only O(cohort x classes) verdict
    inputs — no params leaf — so device memory per block is independent
    of the model size (stack mode pins R post-round param copies)."""
    stack = _build(tiny, runtime=ScanConfig(rounds_per_scan=4,
                                            params_mode="stack"))
    remat = _build(tiny, runtime=ScanConfig(rounds_per_scan=4,
                                            params_mode="remat"))
    s_shapes = stack.block_ys_shapes(4)
    r_shapes = remat.block_ys_shapes(4)
    assert "params" in s_shapes
    assert "params" not in r_shapes
    from repro.core.aggregation import tree_bytes
    params_nbytes = tree_bytes(stack.global_params)
    assert remat.stacked_ys_nbytes(4) < params_nbytes
    assert (stack.stacked_ys_nbytes(4) - remat.stacked_ys_nbytes(4)
            == 4 * params_nbytes)


# ------------------------------------------------------ traced pool carry

def test_scan_pools_traced_folds_bit_for_bit(tiny):
    """The paper's fedentropy composition with selector="pools-traced"
    folds R=4 (no fallback) and reproduces the sequential Server's
    history and params exactly — including through a forced mismatch,
    which must truncate and rebuild the pool carry."""
    data, params = tiny
    seq = fl.build("fedentropy", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20),
                   selector="pools-traced")
    for _ in range(8):
        seq.round()
    for mode in ("stack", "remat"):
        for judge in (None, _WrongScanJudge()):
            scan = _build(
                tiny, "fedentropy",
                runtime=ScanConfig(rounds_per_scan=4, params_mode=mode),
                selector="pools-traced",
                **({} if judge is None else {"judge": judge}))
            assert scan.scan_rounds() == 4
            assert scan.stats()["fallback_reasons"] == []
            assert scan.stats()["pool_fold"] is True
            for _ in range(8):
                scan.round()
            _assert_records_equal(scan.history, seq.history)
            assert _params_digest(scan.global_params) == pytest.approx(
                _params_digest(seq.global_params), rel=DIGEST_REL)
            if judge is not None:
                assert any(not r["spec_hit"] for r in scan.history)


def test_scan_pools_traced_matches_composition_alias(tiny):
    """The "fedentropy-traced" composition is fedentropy with the traced
    pools — same stream as the explicit selector override."""
    a = _build(tiny, "fedentropy-traced",
               runtime=ScanConfig(rounds_per_scan=4))
    b = _build(tiny, "fedentropy", runtime=ScanConfig(rounds_per_scan=4),
               selector="pools-traced")
    assert a.scan_rounds() == b.scan_rounds() == 4
    for _ in range(8):
        a.round()
        b.round()
    _assert_records_equal(a.history, b.history)


# ---------------------------------------------------- eligibility fallback

def test_scan_pools_falls_back_to_sequential(tiny, caplog):
    """Verdict-coupled selectors (pools) cannot fold: R collapses to 1
    with one loud log and the composition still reproduces its golden."""
    with open(GOLDEN) as f:
        golden = json.load(f)["fedentropy"]
    server = _build(tiny, "fedentropy",
                    runtime=ScanConfig(rounds_per_scan=4))
    with caplog.at_level(logging.WARNING,
                         logger="repro.fl.runtime.scan_engine"):
        assert server.scan_rounds() == 1
    assert any("falling back" in r.message for r in caplog.records)
    for _ in range(len(golden["history"])):
        server.round()
    for g, w in zip(server.history, golden["history"]):
        assert g["selected"] == w["selected"]
        assert g["positive"] == w["positive"]
        assert g["negative"] == w["negative"]
        ent = float(w["entropy"])
        if not np.isnan(ent):
            assert g["entropy"] == pytest.approx(ent, abs=ENT_ATOL)
    assert _params_digest(server.global_params) == pytest.approx(
        float(golden["params_digest"]), rel=DIGEST_REL)


def test_scan_stateful_strategy_falls_back(tiny):
    """SCAFFOLD carries cross-round control variates: no fold."""
    server = _build(tiny, "scaffold",
                    runtime=ScanConfig(rounds_per_scan=4))
    assert server.scan_rounds() == 1


@pytest.mark.parametrize("name,code,component", [
    ("fedentropy", "verdict-coupled-selector", "PoolSelector"),
    ("fedentropy+queue", "verdict-coupled-selector", "QueueSelector"),
    ("scaffold", "stateful-strategy", "ScaffoldStrategy"),
    ("fedcat", "group-dispatch", "CatChainStrategy"),
])
def test_scan_fallback_reason_codes(tiny, name, code, component):
    """Every non-foldable composition reports WHY it fell back, machine
    readably: ``fallback_reasons`` dicts with a stable ``code``, the
    offending component class, and prose detail — mirrored in
    ``stats()`` and, per round, on the history record."""
    server = _build(tiny, name, runtime=ScanConfig(rounds_per_scan=4))
    assert server.scan_rounds() == 1
    reasons = server.fallback_reasons
    assert reasons, name
    by_code = {r["code"]: r for r in reasons}
    assert code in by_code
    assert by_code[code]["component"] == component
    assert by_code[code]["detail"]
    assert server.stats()["fallback_reasons"] == reasons
    rec = server.round()
    assert rec["scan_fallback"] == [r["code"] for r in reasons]
    assert code in rec["scan_fallback"]


def test_scan_foldable_composition_reports_no_reasons(tiny):
    """Foldable compositions report an empty reason list — and their
    (folded) records carry no ``scan_fallback`` key."""
    server = _build(tiny, "fedavg",
                    runtime=ScanConfig(rounds_per_scan=4))
    assert server.scan_rounds() == 4
    assert server.fallback_reasons == []
    assert "scan_fallback" not in server.round()


def test_scan_config_rejects_bad_params_mode():
    with pytest.raises(ValueError, match="params_mode"):
        ScanConfig(rounds_per_scan=4, params_mode="checkpoint")


# --------------------------------------------------- device-mode selection

def test_scan_device_selection_deterministic(tiny):
    """selection="device" draws cohorts on device from a carried PRNG key:
    not golden-comparable, but reproducible per seed."""
    cfg = ScanConfig(rounds_per_scan=4, selection="device")
    a = _build(tiny, runtime=cfg)
    b = _build(tiny, runtime=cfg)
    for _ in range(8):
        a.round()
        b.round()
    _assert_records_equal(a.history, b.history)
    assert _params_digest(a.global_params) == pytest.approx(
        _params_digest(b.global_params), rel=1e-12)
    for rec in a.history:
        assert len(rec["selected"]) == 4
        assert len(set(rec["selected"])) == 4          # replace=False
        assert all(0 <= c < 8 for c in rec["selected"])


# ------------------------------------------------------- block semantics

def test_scan_params_advance_block_at_a_time(tiny):
    """One ``round()`` pops one record but the model has already advanced
    through the whole R-round block (the documented trade-off)."""
    data, params = tiny
    seq = fl.build("fedavg", cnn.apply, params, data,
                   fl.ServerConfig(num_clients=8, participation=0.5,
                                   seed=0),
                   LocalSpec(epochs=1, batch_size=20))
    scan = _build(tiny, runtime=ScanConfig(rounds_per_scan=4))
    scan.round()
    assert len(scan.history) == 1
    for _ in range(4):
        seq.round()
    assert _params_digest(scan.global_params) == pytest.approx(
        _params_digest(seq.global_params), rel=DIGEST_REL)


# ------------------------------------------------------- registry matrix

def test_scan_config_validation():
    with pytest.raises(ValueError, match="rounds_per_scan"):
        ScanConfig(rounds_per_scan=0)
    with pytest.raises(ValueError, match="selection"):
        ScanConfig(selection="bogus")


def test_scan_config_routes_without_engine(tiny):
    server = _build(tiny, engine=None, runtime=ScanConfig())
    assert isinstance(server, ScanServer)


def test_engine_runtime_mismatches_error_loudly(tiny):
    with pytest.raises(ValueError, match="ScanConfig"):
        _build(tiny, engine="scan", runtime=RuntimeConfig())
    with pytest.raises(ValueError, match="RuntimeConfig"):
        _build(tiny, engine="pipelined", runtime=ScanConfig())
    with pytest.raises(ValueError, match="ScanConfig"):
        _build(tiny, engine=ScanServer,
               runtime=RuntimeConfig(speculate=True))
