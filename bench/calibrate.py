"""Readings that the limits of ``correct`` are set from, on the chip, in
one process (the benchmark's own runs never run this).

  python3 -m bench.calibrate --workload <cell> --seeds 1 2 ... \\
      [--control-seeds 1 2 3] [--faults unchanged half altered] \\
      [--fault-seeds 1 2 3] [--witness]

For each seed it prints one JSON line per kind of reading:

* ``program`` — the cell's set-up and first checked steps, compared with
  the reference exactly as a run compares them (the lower readings);
* ``control`` — the reference in bfloat16 put in the program's place
  (the upper readings);
* ``fault:<name>`` — the reference in float32 with a fault planted,
  put in the program's place, on ``--fault-seeds`` (by default the
  control's seeds);
* ``witness`` (fleet cells) — the program's own ``client_update`` on
  the first round's smallest client, its rows cut to whole minibatches
  of its own data (no minibatch of padding), against the reference's
  soft label of that client, alongside the padded program's gap.

The last line sums up: the largest program reading and the smallest
control and fault readings of each number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def witness(cfg, wl, seed, cap) -> dict:
    """Soft-label gaps, against the reference, of the program's
    ``client_update`` on one client's unpadded rows and of the padded
    round that the run made."""
    import jax
    import numpy as np
    from repro.core.strategies import LocalSpec, client_update
    from repro.models import cnn
    from . import traffic
    from .drivers import fleet_server as fs
    from .reference import fl as ref
    host = fs._host(cfg, wl, seed)
    sel = cap["selected"][0]
    data = traffic.some_clients(cfg, seed, host, sel)
    p0 = ref.to_program(ref.cnn_init(traffic.jax_key(seed), cfg))
    spec = LocalSpec(epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
                     lr=cfg["lr"], momentum=cfg["momentum"])
    r = ref.run_rounds(cfg, ref.from_program(p0),
                       lambda ids: traffic.some_clients(cfg, seed, host, ids),
                       [cap["verdict"][0]], seed=seed, rounds=1)
    # the cohort's smallest client, whose rows are mostly padding
    i = int(np.argmin([host["counts"][c].sum() for c in sel]))
    n = int(host["counts"][sel[i]].sum())
    rows = -(-n // cfg["batch_size"]) * cfg["batch_size"]
    one = {k: v[i, :rows] for k, v in data.items()}
    out = jax.jit(lambda p, d: client_update(cnn.apply, p, d, spec))(p0, one)
    unpadded = float(np.max(np.abs(
        np.asarray(out["soft_label"], np.float64) - r["soft"][0][i])))
    padded = float(np.max(np.abs(cap["soft"][0][i] - r["soft"][0][i])))
    return {"client": int(sel[i]), "images": n, "unpadded_gap": unpadded,
            "padded_gap": padded}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=None)
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from . import harness
    import jax
    harness.use_cache()
    wl, cfg = harness.cell(args.workload)
    mod = harness.driver(wl["driver"])

    def emit(kind, seed, numbers):
        print(json.dumps({"cell": args.workload, "kind": kind,
                          "seed": seed, **numbers}), flush=True)
        return numbers

    readings: dict[str, list[dict]] = {}
    for seed in args.seeds:
        drv = mod.Driver(cfg, wl, seed)
        for _ in range(harness.CHECK_STEPS):
            drv.step()
        cap = drv.capture()
        drv.close()
        del drv
        readings.setdefault("program", []).append(
            emit("program", seed, mod.compare(cfg, wl, seed, cap)))
        if args.witness:
            emit("witness", seed, witness(cfg, wl, seed, cap))
    fault_seeds = (args.control_seeds if args.fault_seeds is None
                   else args.fault_seeds)
    kinds = [("control", {"dtype": "bfloat16"}, args.control_seeds)] + [
        (f"fault:{f}", {"fault": f}, fault_seeds) for f in args.faults]
    for kind, kw, seeds in kinds:
        for seed in seeds:
            cap = mod.reference_capture(cfg, wl, seed, **kw)
            readings.setdefault(kind, []).append(
                emit(kind, seed, mod.compare(cfg, wl, seed, cap)))
    summary = {}
    for kind, rows in readings.items():
        pick = max if kind == "program" else min
        summary[kind] = {k: pick(r[k] for r in rows) for k in mod.NUMBERS}
    print(json.dumps({"cell": args.workload, "summary": summary,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
