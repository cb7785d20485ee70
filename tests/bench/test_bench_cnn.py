"""The fleet path at a tiny size on the CPU, through the harness's own
run: correct as it stands, not correct with the control in the
program's place, and not correct with each fault planted under the
timed path."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench_testlib import control_correct, run_tiny

CELL = "cnn-cifar10.case1"


def test_runs_and_is_correct():
    r = run_tiny(CELL)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"rounds_per_s", "peak_hbm_bytes",
                                 "setup_s"}


def test_control_in_bfloat16_is_not_correct():
    assert control_correct(CELL, dtype="bfloat16") is False


def _unchanged(monkeypatch):
    from repro.fl import aggregators
    monkeypatch.setattr(aggregators.WeightedAverageAggregator, "__call__",
                        lambda self, g, out, sizes, mask: g)


def _half(monkeypatch):
    from repro.fl import server
    orig = server.client_update

    def half(apply_fn, gp, data, spec, **kw):
        s = data["w"].shape[0]
        keep = (jnp.arange(s) % spec.batch_size) < spec.batch_size // 2
        return orig(apply_fn, gp, dict(data, w=data["w"] * keep), spec,
                    **kw)
    monkeypatch.setattr(server, "client_update", half)


def _altered(monkeypatch):
    from repro.fl import server
    orig = server.client_update

    def altered(*a, **kw):
        out = orig(*a, **kw)
        return dict(out, soft_label=jnp.roll(out["soft_label"], 1))
    monkeypatch.setattr(server, "client_update", altered)


@pytest.mark.parametrize("plant", [_unchanged, _half, _altered],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered"])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    assert run_tiny(CELL)["correct"] is False
