"""One-program rounds: scan-engine throughput vs per-round dispatch.

Three drivers on an N=100-client corpus with deliberately tiny per-round
compute (10 samples/client, a 2-layer MLP instead of the paper CNN), so
the timed quantity is the engines' *per-round overhead* — host
round-trips, selector draws, oracle sync — not the client math:

  * ``sequential`` — the plain ``Server``: one host surfacing per round;
  * ``pipelined``  — ``PipelinedServer`` with verdict speculation ON:
                     still one dispatch per round, but judgment overlaps
                     the next round's client compute;
  * ``scan``       — ``ScanServer`` folding R rounds into ONE jitted
                     ``lax.scan``: the host is touched once per R rounds
                     (selector pre-draw in, oracle verdict replay out).

All three run the same fedentropy composition with the Fig. 3b uniform
selector, so the scan folds and every driver draws the identical cohort
stream — the blob asserts the scan's history (selection/verdict ints)
equals the sequential engine's. The headline is
``speedup_scan_vs_pipelined`` (acceptance gate: >= 2x rounds/sec at
N=100 on CPU).

A second section times the fused (M, P) aggregation
(``core.aggregation.fused_aggregate``, one flat segment-reduce) against
the per-leaf ``masked_mean_tree`` on a CNN pytree (few large leaves), an
LM-like pytree (many small leaves), and the same LM pytree with bf16
leaves — where the gate is the accumulate-dtype contract: the fused
paths must cast to f32 *before* reducing (``accum_f32_ok``: within 2x
the bf16 quantization floor of the exact float64 mean), exactly like
``masked_mean_tree``. On CPU the flatten itself (XLA's many-operand
concatenate) dominates, so the reported ratio prices the copy a
single-launch layout costs there; the launch-count saving the layout
buys is an accelerator property, the numerics contract (tolerance-equal
to the per-leaf mean) is what the suite gates on.

A third section reruns the engine race on the reduced LM fine-tune
workload (qwen3 reduced arch, full-window ``lmstep`` clients, the
``pools-traced`` selector folded into the scan, ``params_mode="remat"``)
— real per-round compute, so the gate is scan >= pipelined rounds/sec,
plus the memory claims: remat's stacked ys carry no params leaf and stay
below one copy of the model (stack mode pins R copies).

Smoke mode (CI): same N=100 corpus, fewer timed rounds, artifact written
to ``BENCH_roundscan.json``:

  PYTHONPATH=src python -m benchmarks.roundscan --smoke \
      --out BENCH_roundscan.json
"""
from __future__ import annotations

import argparse
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import repro.fl as fl
from repro.configs import ARCHS
from repro.core.aggregation import (
    fused_aggregate, masked_mean_tree, tree_bytes,
)
from repro.core.strategies import LocalSpec
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset, make_token_dataset
from repro.fl.runtime import RuntimeConfig, ScanConfig
from repro.launch.train import lm_window_apply, stack_lm_clients
from repro.models import cnn
from repro.models.api import build_model

NUM_CLIENTS = 100
PARTICIPATION = 0.1     # paper's C=0.1 at its N=100 scale
HW = 16
R = 16                  # rounds folded per scan program
LM_R = 8                # fold depth for the LM-arch section


def mlp_init(key, hw: int, num_classes: int) -> dict:
    """Tiny 2-layer MLP honoring the ``apply_fn -> (logits, feats)``
    contract; a LeNet round is ~25ms of conv on CPU, which would bury
    the per-round overhead this benchmark isolates."""
    k1, k2 = jax.random.split(key)
    din, hid = hw * hw * 3, 32
    return {
        "fc1": {"w": jax.random.normal(k1, (din, hid)) *
                jnp.sqrt(2.0 / din), "b": jnp.zeros((hid,))},
        "fc2": {"w": jax.random.normal(k2, (hid, 4)) *
                jnp.sqrt(2.0 / hid), "b": jnp.zeros((4,))},
    }


def mlp_apply(params: dict, x: jax.Array):
    h = x.reshape(x.shape[0], -1)
    feats = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    logits = feats @ params["fc2"]["w"] + params["fc2"]["b"]
    return logits, feats


def make_setup(seed: int = 0):
    """N=100 clients x 10 samples: round overhead dominates compute."""
    (xtr, ytr), _ = make_image_dataset(
        num_classes=4, train_per_class=250, test_per_class=5, hw=HW,
        noise=0.8, seed=seed)
    parts = partition("case1", ytr, NUM_CLIENTS, 4, seed=seed)
    data = stack_clients(xtr, ytr, parts, batch_multiple=10)
    params = mlp_init(jax.random.PRNGKey(seed), HW, 4)
    return data, params


# name -> build kwargs (same composition + selector stream everywhere)
DRIVERS = {
    "sequential": dict(engine=None, runtime=None),
    "pipelined": dict(engine="pipelined",
                      runtime=RuntimeConfig(speculate=True)),
    "scan": dict(engine="scan", runtime=ScanConfig(rounds_per_scan=R)),
}


def time_engines(data, params, rounds: int, repeats: int) -> list[dict]:
    """Best-of-``repeats`` timed blocks of ``rounds`` rounds per driver
    (``rounds`` is a multiple of R so every scan block is full-depth),
    interleaved round-robin so host-load drift hits every driver equally.
    """
    def sync(server):
        jax.block_until_ready(server.global_params)

    servers = {}
    for name, kwargs in DRIVERS.items():
        s = fl.build("fedentropy", mlp_apply, params, data,
                     fl.ServerConfig(num_clients=NUM_CLIENTS,
                                     participation=PARTICIPATION, seed=0),
                     LocalSpec(epochs=1, batch_size=10),
                     selector="uniform", **kwargs)
        for _ in range(R):            # warmup: compile + one full block
            s.round()
        sync(s)
        servers[name] = s
    assert servers["scan"].scan_rounds() == R
    best = {name: float("inf") for name in DRIVERS}
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
        results.append({
            "driver": name, "rounds": rounds, "wall_s": dt,
            "rounds_per_s": rounds / dt, "s_per_round": dt / rounds,
            "repeats": repeats, "history_rounds": len(server.history),
            "spec_hits": sum(1 for h in server.history
                             if h.get("spec_hit"))})
    return results, servers


def histories_match(a, b) -> bool:
    """Selection/verdict int equality over the common prefix."""
    n = min(len(a), len(b))
    return all(a[i]["selected"] == b[i]["selected"]
               and a[i]["positive"] == b[i]["positive"]
               and a[i]["negative"] == b[i]["negative"]
               for i in range(n)) and n > 0


def _lm_like(m: int, seed: int = 0):
    """Many small leaves + one embedding: the launch-count win case."""
    rng = np.random.default_rng(seed)
    tree = {"emb": jnp.asarray(rng.normal(size=(m, 256, 64)), jnp.float32)}
    for i in range(24):
        tree[f"blk{i}"] = {
            "attn": jnp.asarray(rng.normal(size=(m, 64, 64)), jnp.float32),
            "mlp": jnp.asarray(rng.normal(size=(m, 64, 128)), jnp.float32),
            "ln": jnp.asarray(rng.normal(size=(m, 64)), jnp.float32),
        }
    return tree


def _accum_f32_check(tree, sizes, mask) -> tuple[float, float, bool]:
    """Accumulate-dtype gate for low-precision leaves.

    The exact weighted mean is computed in numpy float64; the best any
    f32-accumulating path can do is that mean quantized to the leaf
    dtype. The fused paths must land within 2x that quantization floor —
    accumulating IN bf16 (the bug this gates against) drifts well past
    it, while f32 accumulation + one cast-back sits on it.
    """
    w = np.asarray(sizes, np.float64) * np.asarray(mask, np.float64)
    tot = max(w.sum(), 1e-12)

    def exact(x):
        return np.einsum("m,m...->...", w,
                         np.asarray(x, np.float64)) / tot

    refs = [exact(x) for x in jax.tree.leaves(tree)]
    floor = max(
        float(np.max(np.abs(np.asarray(
            jnp.asarray(r).astype(x.dtype), np.float64) - r)))
        for r, x in zip(refs, jax.tree.leaves(tree)))
    errs = []
    for backend in ("xla", "pallas"):
        got = fused_aggregate(tree, sizes, mask, backend=backend)
        errs.append(max(
            float(np.max(np.abs(np.asarray(g, np.float64) - r)))
            for g, r in zip(jax.tree.leaves(got), refs)))
    err = max(errs)
    return err, floor, bool(err <= 2.0 * floor + 1e-7)


def time_aggregation(repeats: int = 200) -> dict:
    """The per-leaf tree_map mean vs the flat fused reduce, each one
    compiled program."""
    m = 10
    cnn_params = cnn.init(jax.random.PRNGKey(0), image_hw=HW,
                          num_classes=4)
    cnn_tree = jax.tree.map(
        lambda x: jnp.stack([x + 0.01 * i for i in range(m)]), cnn_params)
    lm_tree = _lm_like(m)
    # bf16 leaves: PR 8 made masked_mean_tree accumulate low-precision
    # leaves in f32; the fused paths cast to f32 BEFORE the flatten, so
    # they must meet the same accumulate-dtype contract (gated below)
    lm_bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), lm_tree)
    trees = {"cnn": cnn_tree, "lm": lm_tree, "lm_bf16": lm_bf16}
    sizes = jnp.asarray(np.full(m, 10.0), jnp.float32)
    mask = jnp.asarray(([1.0, 0.0] * m)[:m], jnp.float32)

    tree_fn = masked_mean_tree               # both jitted already
    fused_fn = partial(fused_aggregate, backend="xla")
    out = {}
    for name, tree in trees.items():
        leaves = jax.tree.leaves(tree)
        rec = {"leaves": len(leaves),
               "params": int(sum(x[0].size for x in leaves)),
               "dtype": str(leaves[0].dtype)}
        for label, fn in (("tree", tree_fn), ("fused_xla", fused_fn)):
            jax.block_until_ready(fn(tree, sizes, mask))   # compile
            t0 = time.perf_counter()
            for _ in range(repeats):
                r = fn(tree, sizes, mask)
            jax.block_until_ready(r)
            rec[f"{label}_us"] = (time.perf_counter() - t0) / repeats * 1e6
        # numerics: the Pallas kernel path agrees (interpret mode on CPU
        # is far too slow to time honestly — checked, not raced)
        got = fused_aggregate(tree, sizes, mask, backend="pallas")
        want = masked_mean_tree(tree, sizes, mask)
        rec["pallas_max_err"] = float(max(
            jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))))
        err, floor, ok = _accum_f32_check(tree, sizes, mask)
        rec["accum_err"] = err
        rec["accum_floor"] = floor
        rec["accum_f32_ok"] = ok
        out[name] = rec
    return out


# ---- LM-arch engine section ----------------------------------------------

def make_lm_setup(seed: int = 0):
    """Reduced LM fine-tune workload: the fedentropy composition with the
    scan-foldable pools and the full-window lmstep client rule."""
    cfg = ARCHS["qwen3-0.6b"].reduced().replace(
        remat="none", param_dtype="float32", dtype="float32")
    model = build_model(cfg)
    logical, samples, seq = 8, 4, 16
    corpus, dom = make_token_dataset(
        vocab_size=min(cfg.vocab_size, 512), num_domains=logical,
        docs_per_domain=16, seq_len=seq, seed=seed)
    idx = [np.where(dom == c % logical)[0] for c in range(logical)]
    data = stack_lm_clients(corpus, idx, samples, seq, seed)
    params = model.init(jax.random.PRNGKey(seed))
    return lm_window_apply(model, cfg), data, params


def time_lm_engines(rounds: int, repeats: int) -> tuple[list[dict], dict]:
    """scan (pools folded, remat) vs pipelined vs sequential on the LM
    workload; per-round compute is real here, so the scan's win is the
    removed host surfacing, not free — the gate is >= pipelined."""
    apply_fn, data, params = make_lm_setup(0)
    config = fl.ServerConfig(num_clients=8, participation=0.5, seed=0)
    local = LocalSpec(lr=0.05, epochs=1, batch_size=4)
    drivers = {
        "sequential": dict(engine=None, runtime=None),
        "pipelined": dict(engine="pipelined",
                          runtime=RuntimeConfig(speculate=True)),
        "scan": dict(engine="scan",
                     runtime=ScanConfig(rounds_per_scan=LM_R,
                                        params_mode="remat")),
    }
    servers, best = {}, {}
    for name, kwargs in drivers.items():
        s = fl.build("fedentropy", apply_fn, params, data, config, local,
                     selector="pools-traced", strategy="lmstep", **kwargs)
        for _ in range(2 * LM_R):      # warmup: compile + two full blocks
            s.round()
        jax.block_until_ready(s.global_params)
        servers[name] = s
        best[name] = float("inf")
    scan = servers["scan"]
    assert scan.scan_rounds() == LM_R, scan.fallback_reasons
    for _ in range(repeats):
        for name, server in servers.items():
            t0 = time.perf_counter()
            for _ in range(rounds):
                server.round()
            jax.block_until_ready(server.global_params)
            best[name] = min(best[name], time.perf_counter() - t0)
    results = [{"driver": name, "rounds": rounds, "wall_s": best[name],
                "rounds_per_s": rounds / best[name],
                "s_per_round": best[name] / rounds, "repeats": repeats}
               for name in drivers]
    by = {r["driver"]: r for r in results}
    # memory: remat ys carry no params leaf; a stack-mode twin of the
    # same block (eval_shape only — nothing runs) shows what R copies of
    # the pytree would have pinned
    stack_twin = fl.build(
        "fedentropy", apply_fn, params, data, config, local,
        selector="pools-traced", strategy="lmstep", engine="scan",
        runtime=ScanConfig(rounds_per_scan=LM_R, params_mode="stack"))
    remat_shapes = scan.block_ys_shapes(LM_R)
    blob = {
        "arch": "qwen3-0.6b (reduced)", "rounds_per_scan": LM_R,
        "speedup_scan_vs_pipelined": (by["scan"]["rounds_per_s"] /
                                      by["pipelined"]["rounds_per_s"]),
        "scan_ge_pipelined": (by["scan"]["rounds_per_s"] >=
                              by["pipelined"]["rounds_per_s"]),
        "scan_matches_sequential": histories_match(
            scan.history, servers["sequential"].history),
        "remat_ys_params_free": "params" not in remat_shapes,
        "remat_ys_nbytes": scan.stacked_ys_nbytes(LM_R),
        "stack_ys_nbytes": stack_twin.stacked_ys_nbytes(LM_R),
        "params_nbytes": tree_bytes(params),
        # the LM-scale claim: a remat block's stacked ys stay below even
        # ONE copy of the model, vs R copies in stack mode
        "remat_ys_lt_params": (scan.stacked_ys_nbytes(LM_R) <
                               tree_bytes(params)),
        "mismatch_rounds": scan.stats()["mismatch_rounds"],
    }
    return results, blob


def run(fast: bool = False, smoke: bool = False):
    """Benchmark-harness entry: returns (csv_rows, json_blob)."""
    if smoke or fast:
        rounds, repeats, agg_repeats = 2 * R, 2, 50
        lm_rounds, lm_repeats = 2 * LM_R, 3
    else:
        rounds, repeats, agg_repeats = 4 * R, 5, 200
        lm_rounds, lm_repeats = 4 * LM_R, 3

    data, params = make_setup(0)
    results, servers = time_engines(data, params, rounds, repeats)

    by_name = {r["driver"]: r for r in results}
    speedup = (by_name["scan"]["rounds_per_s"] /
               by_name["pipelined"]["rounds_per_s"])
    match = histories_match(servers["scan"].history,
                            servers["sequential"].history)
    agg = time_aggregation(agg_repeats)
    lm_results, lm = time_lm_engines(lm_rounds, lm_repeats)

    rows = []
    for r in results:
        rows.append((f"roundscan_{r['driver']}",
                     f"{r['s_per_round'] * 1e6:.0f}",
                     f"{r['rounds_per_s']:.2f}rps"))
    for r in lm_results:
        rows.append((f"roundscan_lm_{r['driver']}",
                     f"{r['s_per_round'] * 1e6:.0f}",
                     f"{r['rounds_per_s']:.2f}rps"))
    for name, rec in agg.items():
        rows.append((f"roundscan_agg_{name}", f"{rec['fused_xla_us']:.0f}",
                     f"{rec['tree_us'] / rec['fused_xla_us']:.2f}x1launch"))
    blob = {"results": results, "rounds_per_scan": R,
            "num_clients": NUM_CLIENTS, "participation": PARTICIPATION,
            "speedup_scan_vs_pipelined": speedup,
            # acceptance gate: one program per R rounds beats per-round
            # dispatch by >= 2x when round overhead dominates
            "speedup_ge_2x": speedup >= 2.0,
            "scan_matches_sequential": match,
            "aggregation": agg,
            "lm": {"results": lm_results, **lm},
            "devices": len(jax.devices()),
            "backend": jax.default_backend()}
    return rows, blob


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fewer timed rounds")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default="",
                    help="write the JSON blob here (BENCH_roundscan.json)")
    args = ap.parse_args()
    rows, blob = run(fast=args.fast, smoke=args.smoke)
    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(str(x) for x in r), flush=True)
    print("scan matches sequential:", blob["scan_matches_sequential"])
    print(f"scan vs pipelined: {blob['speedup_scan_vs_pipelined']:.2f}x "
          f"(>=2x: {blob['speedup_ge_2x']})")
    lm = blob["lm"]
    print(f"lm scan vs pipelined: "
          f"{lm['speedup_scan_vs_pipelined']:.2f}x "
          f"(>=1x: {lm['scan_ge_pipelined']}, "
          f"matches sequential: {lm['scan_matches_sequential']})")
    print(f"lm remat ys: {lm['remat_ys_nbytes']}B vs "
          f"{lm['stack_ys_nbytes']}B stacked, params "
          f"{lm['params_nbytes']}B "
          f"(params-free: {lm['remat_ys_params_free']})")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(blob, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
