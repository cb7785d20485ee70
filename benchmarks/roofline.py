"""Roofline table from the multi-pod dry-run artifacts.

Regenerates its records on every run by running
``python -m repro.launch.dryrun --out ...`` in a subprocess (the dry-run
must own a fresh process because it forces 512 host devices before jax
initializes), so the table is built only from committed code, never from
a stale artifact. The child is pinned to the CPU: a parent that has run
tables on a chip holds it, and the dry-run only compiles.

Terms per (arch, shape) on the 16x16 single-pod mesh (TPU v5e constants:
197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link ICI):

  compute_s    = HLO dot-FLOPs(per device, loop-aware)   / 197e12
  memory_s     = HLO operand+result bytes(per device)    / 819e9
  collective_s = collective operand bytes(per device)    / 50e9
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

FAST_COMBOS = [("qwen3-0.6b", "train_4k"), ("mamba2-130m", "decode_32k")]


def _generate(fast: bool) -> list[dict]:
    os.makedirs("results", exist_ok=True)
    records = []
    combos = FAST_COMBOS if fast else [("all", "all")]
    for arch, shape in combos:
        out = f"results/_roofline_tmp_{arch}_{shape}.json"
        subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--out", out],
            check=True, env={**os.environ, "JAX_PLATFORMS": "cpu",
                             "PYTHONPATH": os.environ.get("PYTHONPATH",
                                                          "src")})
        with open(out) as f:
            records += json.load(f)
    return records


def run(fast: bool = False):
    records = _generate(fast)
    rows, blob = [], {"records": []}
    for r in records:
        if r.get("status") != "ok":
            if r.get("status") == "skipped":
                rows.append((f"roofline_{r['arch']}_{r['shape']}", "0",
                             "documented_skip"))
            continue
        t = r["roofline"]
        total = t["compute_s"] + t["memory_s"] + t["collective_s"]
        step_us = max(t["compute_s"], t["memory_s"], t["collective_s"]) * 1e6
        frac = t["compute_s"] / max(total, 1e-12)
        rows.append((
            f"roofline_{r['arch']}_{r['shape']}",
            f"{step_us:.0f}",
            f"dom={t['dominant']}|compute_frac={frac:.3f}"
            f"|useful={r['useful_flops_ratio']:.3f}"
            f"|coll_GB={r['collective_bytes_total'] / 1e9:.2f}"))
        blob["records"].append({k: r[k] for k in
                                ("arch", "shape", "roofline",
                                 "useful_flops_ratio",
                                 "collective_bytes_total",
                                 "collective_counts")})
    return rows, blob
