"""Helpers of the benchmark's CPU tests: the cells at a tiny size, and a
CPU device passed off as a chip of the peaks table (the harness's look
for a chip is the one step these tests skip)."""
from __future__ import annotations

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


class FakeChip:
    """A CPU device that reports a device kind of the peaks table."""

    def __init__(self, dev):
        self.platform = dev.platform
        self.device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def tiny(name: str) -> tuple[dict, dict]:
    """(workload, configuration) of cell ``name`` cut to a CPU test's
    size; the limits stay the cell's own."""
    wl, cfg = harness.cell(name)
    wl, cfg = copy.deepcopy(wl), copy.deepcopy(cfg)
    if cfg["name"] == "cnn-cifar10":
        cfg.update(num_clients=10, train_per_class=100, participation=0.3,
                   local_epochs=1)
        if wl["traffic"]["partition"] == "dirichlet":
            wl["traffic"]["beta"] = 0.5
    else:
        cfg.update(hidden_size=256, num_hidden_layers=2, vocab_size=512,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=32, intermediate_size=384,
                   program_flags=["--reduced"])
        wl["traffic"].update(seq_len=16, logical_clients=8,
                             docs_per_domain=16)
    return wl, cfg


def run_tiny(name: str, seed: int = 2**31 + 5, seconds: float = 0.5):
    import jax
    wl, cfg = tiny(name)
    return harness.run_cell(name, seed, seconds, False,
                            t0=time.perf_counter(),
                            devices=[FakeChip(jax.devices()[0])],
                            wl=wl, cfg=cfg)


def control_correct(name: str, seed: int = 7, **kw) -> bool:
    """Whether the reference put in the program's place (bfloat16, or
    with a planted fault) passes the cell's limits at the tiny size."""
    wl, cfg = tiny(name)
    mod = harness.driver(wl["driver"])
    cap = mod.reference_capture(cfg, wl, seed, **kw)
    numbers = mod.compare(cfg, wl, seed, cap)
    return all(numbers[k] <= wl["limits"][k] for k in mod.NUMBERS)
