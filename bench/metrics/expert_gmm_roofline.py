"""The held experts' grouped products' share of their roofline, in %:
over the traced steps (``moe.route`` spans), the least time the chip
could take, max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s) from each
step's ``rows`` (``bench/flops/moonlight-16b-a3b.py``, the
configuration's shapes; ``bench/peaks.json``), over the products'
device time (``expert_gmm_ms``'s reduction)."""
from __future__ import annotations

import json
import os

from bench import harness
from bench.metrics.expert_gmm_ms import of_run

CONFIG = "moonlight-16b-a3b"


def read(ctx: dict):
    r = of_run(ctx)
    if r is None:
        return None
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    fl = harness.flops_module(CONFIG)
    pk = ctx["peaks"]
    least = sum(max(fl.gmm_flops(cfg, s["rows"]) / pk["bf16_flops_per_s"],
                    fl.gmm_bytes(cfg, s["rows"]) / pk["hbm_bytes_per_s"])
                for s in r["routes"])
    return 100.0 * least / (r["gmm_ns"] * 1e-9)
