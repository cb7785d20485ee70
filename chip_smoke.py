#!/usr/bin/env python3
"""Bring-up check: the FedEntropy main path on a TPU, through its entry
points, at full width.

  python chip_smoke.py              # one chip: phases fleet, kernels, lm
  python chip_smoke.py --chips 4    # four chips: the sharded round only

One process, no subprocess. Each phase prints one line (wall seconds
including compilation, and the device's ``peak_bytes_in_use`` so far);
any failure raises and exits non-zero. The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. On any device
that is not a TPU it prints ``"ok": false`` and exits 1 before running a
phase: it never falls back to the CPU.

Phases (sizes are keyword arguments, so a CPU test can run them small):

* ``fleet`` — the paper's round at paper scale through ``fl.build``:
  the 62,006-parameter CNN on CIFAR-shaped synthetic data, Dirichlet
  non-IID over N=100 clients, 10% participation, E=5, B=50. The
  sequential ``Server`` is the reference; the pipelined engine
  (speculation through the Pallas judge, streaming data plane) and the
  scan engine (remat rounds, Pallas judge) must record the same
  selections and verdicts.
* ``kernels`` — each Pallas kernel of the main path against its
  ``kernels/ref.py`` oracle, with ``tpu_custom_call`` required in the
  compiled program on a TPU (absent elsewhere: the interpreter ran it).
* ``lm`` — qwen3-0.6b at published widths, three gradient-level
  FedEntropy steps through ``repro.launch.train.main`` with the Pallas
  judge; the loss must be finite.
* ``fleet-sharded`` (``--chips 4`` only) — the pipelined engine with the
  cohort sharded over a ("clients",) mesh of all chips against the
  sequential ``Server`` on one of them, in the same process.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (:mod:`repro.compile_cache`).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

import repro.fl as fl
from repro.compile_cache import use_compile_cache
from repro.core.aggregation import fused_aggregate, masked_mean_tree
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.kernels import ref
from repro.kernels.entropy_judge import entropy_judge_sweep
from repro.models import cnn

LM_ARGV = ("--arch", "qwen3-0.6b", "--engine", "mesh",
           "--judge-backend", "pallas", "--steps", "3", "--clients", "4",
           "--per-client-batch", "2", "--seq-len", "128")


class SmokeFailure(AssertionError):
    """A phase's check failed (raised explicitly: survives ``python -O``)."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def _ints(history) -> list:
    return [(r["selected"], r["positive"], r["negative"]) for r in history]


def _entropy_gap(a, b) -> float:
    """Largest |entropy| difference over rounds (NaN = no verdict, equal
    to NaN)."""
    gap = 0.0
    for ra, rb in zip(a, b):
        ea, eb = float(ra["entropy"]), float(rb["entropy"])
        if math.isnan(ea) and math.isnan(eb):
            continue
        gap = max(gap, abs(ea - eb))
    return gap


def _fleet_setup(*, num_clients=100, num_classes=10, train_per_class=500,
                 hw=32, participation=0.1, epochs=5, batch_size=50, seed=0):
    """The paper's round (defaults: CIFAR-shaped, N=100, C=0.1, E=5,
    B=50): (build, (x_test, y_test))."""
    (xtr, ytr), (xte, yte) = make_image_dataset(
        num_classes=num_classes, train_per_class=train_per_class, hw=hw,
        seed=seed)
    parts = partition("dirichlet", ytr, num_clients, num_classes, seed=seed)
    data = stack_clients(xtr, ytr, parts, batch_multiple=batch_size)
    params = cnn.init(jax.random.PRNGKey(seed), image_hw=hw,
                      num_classes=num_classes)
    config = fl.ServerConfig(num_clients=num_clients,
                             participation=participation, seed=seed)
    local = fl.LocalSpec(epochs=epochs, batch_size=batch_size)

    def build(**kw):
        return fl.build("fedentropy", cnn.apply, params, data, config,
                        local, **kw)
    return build, (jnp.asarray(xte), jnp.asarray(yte))


def _run(server, rounds: int, test) -> dict:
    for _ in range(rounds):
        server.round()
    _check(_finite(server.global_params), "non-finite global params")
    acc = float(server.evaluate(*test)["accuracy"])
    _check(math.isfinite(acc), f"non-finite accuracy {acc}")
    return {"history": server.history, "accuracy": acc}


def _same_ints(name: str, got: dict, want: dict) -> None:
    _check(_ints(got["history"]) == _ints(want["history"]),
           f"{name}: selection/verdict ints differ from the reference:\n"
           f"  got  {_ints(got['history'])}\n"
           f"  want {_ints(want['history'])}")


def phase_fleet(*, rounds=4, **setup) -> str:
    build, test = _fleet_setup(**setup)
    # (a) the sequential Server: the reference
    seq = _run(build(), rounds, test)
    # (b) pipelined: Pallas-judge speculation over the streaming plane
    pipe = _run(build(engine="pipelined",
                      runtime=fl.RuntimeConfig(speculate=True,
                                               spec_backend="pallas"),
                      data_plane="streaming"), rounds, test)
    _same_ints("pipelined", pipe, seq)
    # (c) jax.random-stream pools: sequential vs one R-round scan block
    seq_t = _run(build(selector="pools-traced"), rounds, test)
    scan = _run(build(selector="pools-traced", engine="scan",
                      runtime=fl.ScanConfig(rounds_per_scan=rounds,
                                            params_mode="remat",
                                            spec_backend="pallas")),
                rounds, test)
    _same_ints("scan", scan, seq_t)
    gaps = (_entropy_gap(pipe["history"], seq["history"]),
            _entropy_gap(scan["history"], seq_t["history"]))
    pos = [len(r["positive"]) for r in seq["history"]]
    return (f"ints equal over {rounds} rounds; entropy gap "
            f"pipelined-vs-sequential={gaps[0]:.3e} "
            f"scan-vs-sequential={gaps[1]:.3e}; positives/round={pos}; "
            f"accuracy seq={seq['accuracy']:.4f} "
            f"pipelined={pipe['accuracy']:.4f} "
            f"traced={seq_t['accuracy']:.4f} scan={scan['accuracy']:.4f}")


def phase_fleet_sharded(*, chips=4, rounds=4, **setup) -> str:
    devices = jax.devices()
    _check(len(devices) == chips, f"need {chips} devices, have {devices}")
    build, test = _fleet_setup(**setup)
    seq = _run(build(), rounds, test)
    # the default client mesh spans every chip the process sees
    server = build(engine="pipelined",
                   runtime=fl.RuntimeConfig(shard=True, speculate=True))
    sharded = _run(server, rounds, test)
    _same_ints("sharded pipelined", sharded, seq)
    # the resident corpus is laid out P("clients") over every chip
    x = server.corpus["x"]
    rows = {s.device: s.data.shape[0] for s in x.addressable_shards}
    _check(set(rows) == set(devices), f"corpus on {sorted(map(str, rows))}")
    _check(len(set(rows.values())) == 1 and sum(rows.values()) == x.shape[0],
           f"corpus rows per chip {rows}")
    cohort = server.config.cohort_size()
    return (f"ints equal over {rounds} rounds on {chips} chips; cohort "
            f"{cohort} padded to {-(-cohort // chips) * chips}; corpus "
            f"{x.shape[0]} rows ({server.config.num_clients} clients), "
            f"{next(iter(rows.values()))} per chip; entropy gap "
            f"sharded-vs-sequential="
            f"{_entropy_gap(sharded['history'], seq['history']):.3e}; "
            f"accuracy seq={seq['accuracy']:.4f} "
            f"sharded={sharded['accuracy']:.4f}")


def _compiled(fn, *args):
    """Compile ``fn`` for ``args``; require a Mosaic kernel on a TPU and
    none elsewhere (the Pallas interpreter lowers to plain HLO)."""
    comp = jax.jit(fn).lower(*args).compile()
    has_kernel = "tpu_custom_call" in comp.as_text()
    _check(has_kernel == _on_tpu(),
           f"tpu_custom_call present={has_kernel} on "
           f"{jax.devices()[0].platform}")
    return comp


def phase_kernels(*, judge_shapes=((10, 10), (8, 151936)), agg_clients=10,
                  hw=32, seed=0) -> str:
    key = jax.random.PRNGKey(seed)
    notes = []
    for m, c in judge_shapes:
        k1, k2, key = jax.random.split(key, 3)
        soft = jax.nn.softmax(3.0 * jax.random.normal(k1, (m, c)), axis=-1)
        sizes = jax.random.randint(k2, (m,), 10, 500).astype(jnp.float32)
        mask = jnp.ones((m,), jnp.float32).at[m // 2].set(0.0)
        ent, loo = _compiled(entropy_judge_sweep, soft, sizes, mask)(
            soft, sizes, mask)
        ent0, loo0 = jax.jit(ref.entropy_judge_sweep_reference)(
            soft, sizes, mask)
        gap = max(float(jnp.abs(ent - ent0)),
                  float(jnp.max(jnp.abs(loo - loo0))))
        _check(gap < 1e-4, f"entropy_judge_sweep ({m}, {c}) gap {gap}")
        notes.append(f"judge({m},{c}) gap={gap:.3e}")

    k1, k2, key = jax.random.split(key, 3)
    stacked = jax.vmap(lambda k: cnn.init(k, image_hw=hw))(
        jax.random.split(k1, agg_clients))
    p = sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(stacked))
    sizes = jax.random.randint(k2, (agg_clients,), 10, 500
                               ).astype(jnp.float32)
    mask = jnp.ones((agg_clients,), jnp.float32).at[0].set(0.0)
    fused = _compiled(lambda t, s, w: fused_aggregate(t, s, w,
                                                      backend="pallas"),
                      stacked, sizes, mask)(stacked, sizes, mask)
    want = jax.jit(masked_mean_tree)(stacked, sizes, mask)
    gap = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(want)))
    _check(gap < 1e-5, f"fused_aggregate ({agg_clients}, {p}) gap {gap}")
    notes.append(f"fused_aggregate({agg_clients},{p}) gap={gap:.3e}")
    return "; ".join(notes)


def phase_lm(*, argv=LM_ARGV) -> str:
    from repro.launch import train
    records = train.main(list(argv))
    losses = [r["loss"] for r in records]
    _check(bool(losses) and all(math.isfinite(v) for v in losses),
           f"non-finite loss {losses}")
    return (f"{len(losses)} steps, loss=" +
            ",".join(f"{v:.4f}" for v in losses) +
            f"; positives={[int(r['num_positive']) for r in records]}")


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the cohort-sharded round on four "
                         "chips against the one-chip sequential Server")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(json.dumps({"ok": False, "device": device,
                          "error": f"needs {args.chips} TPU chip(s)"}))
        return 1
    cache = use_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache}", flush=True)

    if args.chips == 4:
        phases = [("fleet-sharded", phase_fleet_sharded)]
    else:
        phases = [("fleet", phase_fleet), ("kernels", phase_kernels),
                  ("lm", phase_lm)]
    for name, fn in phases:
        t0 = time.perf_counter()
        note = fn()
        gc.collect()     # free the phase's device buffers before the next
        print(f"phase {name}: wall_s={time.perf_counter() - t0:.1f} "
              f"peak_bytes_in_use={_peak_bytes(devices)} | {note}",
              flush=True)
    entries = sum(len(files) for _, _, files in os.walk(cache))
    print(f"compile cache {cache}: {entries} files", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
