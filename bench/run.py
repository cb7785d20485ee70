"""Run one cell of the chip benchmark once.

  python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

From the root of a checkout. Prints the result as one JSON line, the last
line of standard output; the numbers the check compared, each beside its
limit, are the last lines of standard error. Exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from . import harness
    wl, _ = harness.cell(args.workload)
    import jax
    harness.use_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"bench: cell {args.workload} needs {wl['chips']} TPU "
              f"chip(s); JAX has {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t0=T0,
                              devices=devices[:wl["chips"]])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
