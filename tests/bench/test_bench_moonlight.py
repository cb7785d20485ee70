"""The Moonlight cell (``moonlight-16b-a3b.silo4-seq256``, path
``lm_mesh_moe``) at a tiny size on the CPU through the harness's own
run: correct as it stands, not correct with the bfloat16 control in the
program's place or with each fault planted under the timed path; its
parameter count by hand; and its two per-layer readers, on a trace made
by hand and on two steps traced on a TPU v5e."""
from __future__ import annotations

import copy
import os
import time

import jax
import pytest

from bench_testlib import FakeChip, harness
from bench.peaks import peaks
from test_bench_lm import _altered, _half, _unchanged

CELL = "moonlight-16b-a3b.silo4-seq256"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_moonlight")


def tiny() -> tuple[dict, dict]:
    """The cell at a CPU test's size: the program's reduced widths, one
    dense and two expert layers, 2 of 4 experts and half the vocabulary
    held; the limits stay the cell's own."""
    wl, cfg = map(copy.deepcopy, harness.cell(CELL))
    cfg.update(hidden_size=256, num_hidden_layers=3, vocab_size=256,
               num_attention_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, intermediate_size=384,
               moe_intermediate_size=128, n_routed_experts=2,
               expert_parallel=2, num_experts_per_tok=2,
               program_flags=["--reduced", "--expert-parallel", "2",
                              "--num-layers", "3"])
    wl["traffic"].update(seq_len=16, logical_clients=8, docs_per_domain=16)
    return wl, cfg


def run_tiny(seed: int = 2**31 + 5, seconds: float = 0.5) -> dict:
    wl, cfg = tiny()
    return harness.run_cell(CELL, seed, seconds, False,
                            t0=time.perf_counter(),
                            devices=[FakeChip(jax.devices()[0])],
                            wl=wl, cfg=cfg)


def test_runs_and_is_correct():
    r = run_tiny()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and list(r)[-1] == "checks"


def test_control_in_bfloat16_is_not_correct():
    wl, cfg = tiny()
    mod = harness.driver(wl["driver"])
    cap = mod.reference_capture(cfg, wl, 7, dtype="bfloat16")
    numbers = mod.compare(cfg, wl, 7, cap)
    assert not all(numbers[k] <= wl["limits"][k] for k in mod.NUMBERS)


@pytest.mark.parametrize("plant", [_unchanged, _half, _altered],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered"])
def test_a_fault_under_the_timed_path_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    jax.clear_caches()
    assert run_tiny()["correct"] is False


def test_matmul_params_by_hand():
    _, cfg = harness.cell(CELL)
    fl = harness.flops_module("moonlight-16b-a3b")
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert mla == 13_762_560
    dense = mla + 3 * 2048 * 11264
    moe = mla + 2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    assert (dense, moe) == (82_968_576, 100_401_152)
    hand = dense + 5 * moe + 2 * 20480 * 2048
    assert fl.matmul_params(cfg) == hand == cfg["matmul_params"] == \
        668_860_416
    # one token, one position, one routed assignment
    attn = 3 * 2 * 16 * (128 + 64 + 128) * 1 * 6
    assert fl.step_flops(cfg, 1, 1, 1) == \
        6 * (hand - 5 * 8 * 3 * 2048 * 1408) + attn + 6 * 3 * 2048 * 1408


class _Ev:
    def __init__(self, name, start, end, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, end - start
        self.stats = list(stats)


class _Named:
    def __init__(self, name, items, key):
        self.name = name
        setattr(self, key, items)


def _trace(host, device):
    line = lambda name, evs: _Named(name, evs, "events")
    return _Named("", [
        _Named("/host:CPU", [line("main", host)], "lines"),
        _Named("/device:TPU:0", [line("XLA Ops", device)], "lines")],
        "planes")


def test_readers_by_hand(monkeypatch):
    """Two steps in the window, one keyword-encoded in the name; a
    grouped product outside the window and a span after it are not
    read."""
    from bench.metrics import expert_gmm_ms as gm
    host = [_Ev("bench_window", 0, 1000),
            _Ev("moe.route", 100, 101, [("rows", 6000), ("max_rows", 900),
                                        ("min_rows", 500)]),
            _Ev("moe.route#rows=6200,max_rows=950,min_rows=480#", 600, 601),
            _Ev("moe.route", 1200, 1201, [("rows", 1)])]
    dev = [_Ev("%gmm.1 = f32[..] custom-call(..)", 10, 40),
           _Ev("%tgmm = f32[..] custom-call(..)", 40, 42),
           _Ev("%fusion.3 = f32[..] fusion(..)", 42, 90),
           _Ev("%gmm.2 = f32[..] custom-call(..)", 1100, 1150)]
    r = gm.reduce(_trace(host, dev))
    assert [s["rows"] for s in r["routes"]] == [6000, 6200]
    assert r["routes"][1] == {"rows": 6200, "max_rows": 950,
                              "min_rows": 480}
    assert r["gmm_ns"] == 32
    monkeypatch.setattr(gm, "of_run", lambda ctx: r)
    assert gm.read({}) == pytest.approx(16e-6)
    from bench.metrics import expert_gmm_roofline as rl
    monkeypatch.setattr(rl, "of_run", lambda ctx: r)
    _, cfg = harness.cell(CELL)
    fl = harness.flops_module("moonlight-16b-a3b")
    pk = peaks("TPU v5 lite")
    least = sum(max(fl.gmm_flops(cfg, n) / pk["bf16_flops_per_s"],
                    fl.gmm_bytes(cfg, n) / pk["hbm_bytes_per_s"])
                for n in (6000, 6200))
    assert rl.read({"peaks": pk}) == pytest.approx(100 * least / 32e-9)


def test_readers_read_the_recorded_steps():
    """Two steps of the cell traced on a TPU v5e (seed 3150000021; the
    device's ``XLA Ops``, ``XLA Modules`` and ``Steps`` lines and the
    host's ``python3`` thread kept): the grouped products are found by
    name, their time per counter span is the events' own, and the
    roofline share lies between 0 and 100%."""
    from bench import spans, trace
    pd = trace.load(DATA)
    lo, hi = spans.window(pd)
    ops = next(iter(trace.device_ops(pd).values()))
    names = {trace.op_name(n).split(".")[0] for _, _, n in ops}
    assert {"gmm", "tgmm"} <= names
    by_hand = sum(e - s for s, e, n in ops if lo <= s and e <= hi and
                  trace.op_name(n).startswith(("gmm", "tgmm")))
    ctx = {"trace": {"window_s": 1.0}, "trace_dir": DATA,
           "peaks": peaks("TPU v5 lite")}
    from bench.metrics import expert_gmm_ms as gm
    assert [s["rows"] for s in gm.of_run(ctx)["routes"]] == [7082, 6104]
    ms = harness.metric_reader("expert_gmm_ms")(ctx)
    assert ms == pytest.approx(by_hand * 1e-6 / 2)
    assert 0 < harness.metric_reader("expert_gmm_roofline")(ctx) < 100


def test_no_counter_span_reads_nothing():
    """A trace of a program without the counter span (the qwen3 cell's,
    or the parent's): both readers return None, neither raises."""
    from bench.metrics import expert_gmm_ms as gm
    pd = _trace([_Ev("bench_window", 0, 1000)],
                [_Ev("%fusion.3 = f32[..] fusion(..)", 42, 90)])
    assert gm.reduce(pd)["routes"] == []
    ctx = {"trace": {"window_s": 1.0},
           "trace_dir": os.path.join(os.path.dirname(DATA), "v5e_case1"),
           "peaks": peaks("TPU v5 lite")}
    assert harness.metric_reader("expert_gmm_ms")(ctx) is None
    assert harness.metric_reader("expert_gmm_roofline")(ctx) is None
