"""Engine throughput: sequential ``Server`` vs the runtime engines.

Two compositions — fedentropy (pools + maxent + weighted FedAvg) and
fedcat+maxent (entropy-grouped device chains + maxent + concatenation
merge, where the *group* is the dispatch unit) on the reduced CNN
corpus — three drivers each:

  * ``sequential``    — ``repro.fl.Server`` (the baseline round loop);
  * ``pipelined``     — ``PipelinedServer``, speculation off (sharding
                        "auto": identical program on one device, shard_map
                        client fan-out on many);
  * ``pipelined+spec``— speculation on: the float64 judgment oracle
                        overlaps the next round's in-flight client compute,
                        device verdict via the traced judge.

The process-level compile cache is enabled for the sweep, so the three
servers (same apply_fn/spec/shapes) share one compiled ClientUpdate —
the recompile-per-server cost the cache exists to kill is reported as
cache stats in the JSON blob.

Smoke mode (CI): best-of-5 blocks of 5 rounds each on a tiny 8-client
composition (~30 s total), artifact written to ``BENCH_engine.json`` so
the perf trajectory accumulates per commit.

  PYTHONPATH=src python -m benchmarks.engine_throughput --smoke \
      --out BENCH_engine.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax

import repro.fl as fl
from repro.core.strategies import LocalSpec
from repro.fl.runtime import (
    RuntimeConfig, disable_process_cache, enable_process_cache,
    process_cache,
)

from .common import make_setup

ENGINES = {
    "sequential": dict(engine=None, runtime=None),
    "pipelined": dict(engine="pipelined", runtime=RuntimeConfig()),
    "pipelined+spec": dict(engine="pipelined",
                           runtime=RuntimeConfig(speculate=True)),
}


def _build(name: str, setup, local: LocalSpec, num_clients: int,
           participation: float, apply_fn, composition: str = "fedentropy"):
    data, params, _ = setup
    return fl.build(composition, apply_fn, params, data,
                    fl.ServerConfig(num_clients=num_clients,
                                    participation=participation, seed=0),
                    local, **ENGINES[name])


def time_engines(setup, local: LocalSpec, num_clients: int,
                 participation: float, apply_fn, rounds: int,
                 repeats: int = 5,
                 composition: str = "fedentropy") -> list[dict]:
    """Best-of-``repeats`` timed blocks of ``rounds`` rounds per engine,
    INTERLEAVED round-robin across engines so host-load drift hits every
    engine equally (spec-off pipelined runs the identical compiled program
    the sequential server does — any difference is measurement noise)."""
    def sync(server):
        """Drain ALL in-flight work, including a speculatively dispatched
        next round — otherwise a pending dispatch leaks its compute into
        the next engine's timed block."""
        jax.block_until_ready(server.global_params)
        pending = getattr(server, "_pending", None)
        if pending is not None:
            jax.block_until_ready(pending[-1])   # (sel, cids, out)

    servers = {}
    for name in ENGINES:
        s = _build(name, setup, local, num_clients, participation, apply_fn,
                   composition)
        s.round()                             # warmup: compile + dispatch
        sync(s)
        servers[name] = s
    best = {name: float("inf") for name in ENGINES}
    for _ in range(repeats):
        for name, server in servers.items():
            t0 = time.perf_counter()
            for _ in range(rounds):
                server.round()
            sync(server)
            best[name] = min(best[name], time.perf_counter() - t0)
    results = []
    for name, server in servers.items():
        dt = best[name]
        rec = {"engine": name, "rounds": rounds, "wall_s": dt,
               "rounds_per_s": rounds / dt, "s_per_round": dt / rounds,
               "repeats": repeats}
        hits = [h.get("spec_hit") for h in server.history
                if "spec_hit" in h]
        if hits:
            rec["spec_hit_rate"] = sum(hits) / len(hits)
            rec["redispatched"] = sum(
                1 for h in server.history if h.get("redispatched"))
        results.append(rec)
    return results


def run(fast: bool = False, smoke: bool = False):
    """Benchmark-harness entry: returns (csv_rows, json_blob)."""
    from repro.models import cnn

    if smoke:
        num_clients, participation, rounds = 8, 0.5, 5
        local = LocalSpec(epochs=1, batch_size=20)
    elif fast:
        num_clients, participation, rounds = 16, 0.25, 5
        local = LocalSpec(epochs=1, batch_size=24)
    else:
        num_clients, participation, rounds = 32, 0.156, 20
        local = LocalSpec(epochs=2, batch_size=24)

    setup = make_setup("case1", 0)
    if smoke or fast:   # trim the corpus to the reduced client count
        data, params, test = setup
        data = {k: v[:num_clients] for k, v in data.items()}
        setup = (data, params, test)

    enable_process_cache(maxsize=16)
    try:
        sweeps = {"fedentropy": time_engines(
            setup, local, num_clients, participation, cnn.apply, rounds)}
        sweeps["fedcat+maxent"] = time_engines(
            setup, local, num_clients, participation, cnn.apply, rounds,
            composition="fedcat+maxent")
        cache_stats = process_cache().stats()
    finally:
        disable_process_cache()

    rows, results = [], []
    for comp, res in sweeps.items():
        base = next(r for r in res if r["engine"] == "sequential")
        prefix = "engine" if comp == "fedentropy" else "engine_fedcat"
        for r in res:
            r["composition"] = comp
            r["speedup_vs_sequential"] = (r["rounds_per_s"] /
                                          base["rounds_per_s"])
            rows.append((f"{prefix}_{r['engine']}",
                         f"{r['s_per_round'] * 1e6:.0f}",
                         f"{r['rounds_per_s']:.3f}rps"))
            results.append(r)
    blob = {"results": results, "compile_cache": cache_stats,
            "num_clients": num_clients, "participation": participation,
            "rounds": rounds, "devices": len(jax.devices()),
            "backend": jax.default_backend()}
    return rows, blob


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny composition, 5-round blocks")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default="",
                    help="write the JSON blob here (BENCH_engine.json)")
    args = ap.parse_args()
    rows, blob = run(fast=args.fast, smoke=args.smoke)
    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(str(x) for x in r), flush=True)
    print("compile cache:", blob["compile_cache"])
    if args.out:
        with open(args.out, "w") as f:
            json.dump(blob, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
