"""The reduction from a profiler trace to busy time, idle share and the
breakdown: on intervals by hand, and on a trace recorded on a TPU v5e."""
from __future__ import annotations

import os

import pytest

from bench_testlib import harness
from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_is_the_union_of_overlapping_ops():
    ops = [(10, 20, "a"), (15, 30, "b"), (40, 50, "c"), (45, 48, "d"),
           (90, 120, "e")]
    busy, gaps = trace.busy_and_gaps(ops, 0, 100)
    assert busy == (30 - 10) + (50 - 40) + (100 - 90)
    assert gaps == [(0, 10), (30, 40), (50, 90)]


def test_idle_share_metric_reads_the_reduction():
    read = harness.metric_reader("device_idle_share")
    assert read({"trace": {"busy_s": 0.75, "window_s": 1.0}}) == 25.0
    assert read({}) is None


def test_mfu_is_flops_over_window_chips_and_peak():
    read = harness.metric_reader("mfu")
    ctx = {"trace": {"window_s": 2.0}, "traced_flops": 197e12,
           "chips": 1, "peaks": {"bf16_flops_per_s": 197e12}}
    assert read(ctx) == 50.0
    assert read(dict(ctx, traced_flops=0)) is None


def test_round_p95_needs_enough_rounds():
    read = harness.metric_reader("round_ms_p95")
    assert read({"round_returns": [0.0, 0.01, 0.02]}) is None
    t = [i * 0.010 for i in range(101)]
    assert read({"round_returns": t}) == pytest.approx(10.0)


def test_self_time_leaves_out_nested_operations():
    ops = [(0, 100, "%while.1 = (...) while(...)"), (10, 30, "%fusion.2 = f"),
           (40, 50, "%fusion.2 = f"), (120, 130, "%copy.3 = c")]
    assert trace.self_times(ops) == {"while.1": 70, "fusion.2": 30,
                                     "copy.3": 10}


def test_reduction_of_a_recorded_v5e_trace():
    """Two rounds of cnn-cifar10.case1 traced on one TPU v5e."""
    pd = trace.load(os.path.join(DATA, "v5e_case1"))
    r = trace.reduce(pd, ("round",))
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) == 10 and 1 <= len(r["idle_gaps"]) <= 10
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True)
    # self times never sum past the time the chip was busy
    assert sum(times) <= r["busy_s"] * 1.0001
    gaps = [t for _, t in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) == pytest.approx(r["window_s"] - r["busy_s"],
                                      rel=1e-6) or len(gaps) == 10
    assert {n for n, _ in r["idle_gaps"]} <= {"round", "none"}
