"""FLOP counts from shapes against hand counts, and the peaks table."""
from __future__ import annotations

import pytest

from bench_testlib import harness
from bench.peaks import UnknownDevice, peaks


def test_cnn_forward_macs_by_hand():
    _, cfg = harness.cell("cnn-cifar10.case1")
    fl = harness.flops_module("cnn-cifar10")
    # conv1 28*28*6*(5*5*3) + conv2 10*10*16*(5*5*6) + 400*120 + 120*84
    # + 84*10, Appendix Table 5 on 32x32x3
    hand = 352_800 + 240_000 + 48_000 + 10_080 + 840
    assert fl.forward_macs(cfg) == hand == 651_720
    # E=5 epochs of fwd+bwd (3 forwards) plus one soft-label forward
    assert fl.round_flops(cfg, 5000) == 5000 * (5 * 3 + 1) * 2 * hand


def test_qwen3_matmul_params_by_hand():
    _, cfg = harness.cell("qwen3-0.6b.silo4")
    fl = harness.flops_module("qwen3-0.6b")
    per_layer = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                 + 3 * 1024 * 3072)
    assert fl.matmul_params(cfg) == 28 * per_layer + 1024 * 151936
    assert round(fl.matmul_params(cfg) / 1e6) == 596
    # one token, one position: no attention beyond itself
    attn = 3 * 2 * 2 * 16 * 128 * 1 * 28
    assert fl.step_flops(cfg, 1, 1) == 6 * fl.matmul_params(cfg) + attn


def test_peaks_table_has_the_v5e_and_refuses_unknown_kinds():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(UnknownDevice):
        peaks("cpu")
