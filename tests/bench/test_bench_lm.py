"""The cross-silo LM path at a tiny size on the CPU, through the
harness's own run: correct as it stands, not correct with the control
in the program's place, and not correct with each fault planted under
the timed path."""
from __future__ import annotations

import jax
import pytest

from bench_testlib import control_correct, run_tiny

CELL = "qwen3-0.6b.silo4"


def test_runs_and_is_correct():
    r = run_tiny(CELL)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and list(r)[-1] == "checks"


def test_control_in_bfloat16_is_not_correct():
    assert control_correct(CELL, dtype="bfloat16") is False


def _unchanged(monkeypatch):
    from repro.launch import train
    from repro.optim import Optimizer
    orig = train.sgd

    def frozen(**kw):
        opt = orig(**kw)
        return Optimizer(opt.init, lambda g, s, p: (p, s))
    monkeypatch.setattr(train, "sgd", frozen)


def _half(monkeypatch):
    from repro.launch import train
    orig = train.make_train_step

    def half(model, opt, fed, judge_fn=None):
        step = orig(model, opt, fed, judge_fn=judge_fn)

        def first_half(params, opt_state, batch):
            t = batch["tokens"]
            per = t.shape[0] // fed.num_clients
            t = t.reshape(fed.num_clients, per, -1)[:, : per // 2]
            return step(params, opt_state,
                        dict(batch, tokens=t.reshape(-1, t.shape[-1])))
        return first_half
    monkeypatch.setattr(train, "make_train_step", half)


def _altered(monkeypatch):
    from repro.fl import judges
    orig = judges.MaxEntropyJudge.traced

    def flipped(self):
        inner = orig(self)

        def judge(soft, sizes):
            r = inner(soft, sizes)
            return r._replace(mask=r.mask.at[0].set(1.0 - r.mask[0]))
        return judge
    monkeypatch.setattr(judges.MaxEntropyJudge, "traced", flipped)


@pytest.mark.parametrize("plant", [_unchanged, _half, _altered],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered"])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    jax.clear_caches()
    assert run_tiny(CELL)["correct"] is False
