"""Device memory of the mesh engine's LM train step, from the compiler.

  python -m benchmarks.lm_step_memory                       # attached chip
  python -m benchmarks.lm_step_memory --describe v5e:2x2    # no chip
  python -m benchmarks.lm_step_memory --run                 # + one step each

For each ``--seq-len`` it compiles the step ``repro.launch.train --engine
mesh`` runs with ``chip_smoke.py``'s lm flags (qwen3-0.6b at published
widths, 4 clients x 2 sequences, Pallas judge) and prints one JSON line
of ``compiled.memory_analysis()``: argument, output, alias and temporary
bytes, and ``total`` = argument + output - alias + temporary, the HBM the
program needs; where the compiler refuses the program for HBM, its first
error line instead. ``--describe`` compiles for one chip of a described TPU
topology instead of the attached device; no chip is needed, and the
Pallas judge is then traced in interpret mode (its buffers are
O(clients x vocab), negligible here). ``--run`` also runs one step at
each length the compiler accepted, on the attached device through
``repro.launch.train.main``, shortest first, and prints the device's
``memory_stats()`` after it.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np
from jax.sharding import Mesh

from repro.compile_cache import use_compile_cache
from repro.launch import train

LM_ARGV = ("--arch", "qwen3-0.6b", "--engine", "mesh",
           "--judge-backend", "pallas", "--clients", "4",
           "--per-client-batch", "2")


def _one_chip_mesh(devices) -> Mesh:
    return Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seq-len", type=int, nargs="+", default=[128, 256])
    ap.add_argument("--describe", default="",
                    help="compile for one chip of this TPU topology "
                         "(e.g. v5e:2x2) instead of the attached device")
    ap.add_argument("--run", action="store_true",
                    help="also run one step per accepted length on the "
                         "attached device and print its memory_stats()")
    args = ap.parse_args(argv)
    if args.describe:
        from jax.experimental import topologies
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=args.describe).devices
        target = f"described {args.describe}"
    else:
        use_compile_cache()
        devices = jax.devices()
        target = devices[0].device_kind
    limit = None if args.describe else (
        devices[0].memory_stats() or {}).get("bytes_limit")
    accepted = []
    for seq in sorted(args.seq_len):
        flags = LM_ARGV + ("--seq-len", str(seq))
        rec = {"target": target, "seq_len": seq, "bytes_limit": limit}
        try:
            rec.update(train.mesh_step_memory(flags, _one_chip_mesh(devices)))
        except jax.errors.JaxRuntimeError as e:
            # the compiler refuses a program that does not fit HBM; its
            # first line says by how much
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            rec["refused"] = str(e).splitlines()[0]
        else:
            accepted.append(seq)
        print(json.dumps(rec), flush=True)
    if args.run:
        for seq in accepted:
            train.main(list(LM_ARGV) + ["--steps", "1",
                                        "--seq-len", str(seq)])
            print(json.dumps({"target": target, "seq_len": seq,
                              "ran": True,
                              "memory_stats": devices[0].memory_stats()}),
                  flush=True)


if __name__ == "__main__":
    main()
