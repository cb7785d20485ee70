"""Benchmark runner — one function per paper table/figure + roofline.

Emits ``name,us_per_call,derived`` CSV rows per the harness contract, where
``derived`` carries the table's headline quantity (accuracy delta, byte
savings, ...). Full JSON results land in results/bench_*.json.

  PYTHONPATH=src python -m benchmarks.run               # all tables
  PYTHONPATH=src python -m benchmarks.run table1        # one table
Options: --fast (1 seed, fewer rounds) for CI-speed runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.compile_cache import use_compile_cache

from . import ablation_fig3, accuracy_table1, async_throughput, \
    comm_table2, dataplane_bench, engine_throughput, microbench, roofline, \
    roundscan, stream_bench, synergy_table3

TABLES = {
    "table1": accuracy_table1.run,
    "table2": comm_table2.run,
    "table3": synergy_table3.run,
    "fig3": ablation_fig3.run,
    "micro": microbench.run,
    "roofline": roofline.run,
    "engine": engine_throughput.run,
    "dataplane": dataplane_bench.run,
    "async": async_throughput.run,
    "stream": stream_bench.run,
    "roundscan": roundscan.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("tables", nargs="*", default=[],
                    help=f"subset of {sorted(TABLES)} (default: all)")
    ap.add_argument("--fast", action="store_true",
                    help="1 seed / reduced rounds")
    ap.add_argument("--out-dir", default="results")
    args = ap.parse_args()
    use_compile_cache()

    names = args.tables or list(TABLES)
    os.makedirs(args.out_dir, exist_ok=True)
    print("name,us_per_call,derived")
    ok = True
    for name in names:
        try:
            rows, blob = TABLES[name](fast=args.fast)
            for r in rows:
                print(",".join(str(x) for x in r), flush=True)
            with open(os.path.join(args.out_dir, f"bench_{name}.json"),
                      "w") as f:
                json.dump(blob, f, indent=1, default=str)
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
            ok = False
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
