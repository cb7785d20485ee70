"""Path ``lm_mesh``: the gradient-level FedEntropy step of
``repro.launch.train``'s default mesh engine.

The jitted step comes from ``launch.train.build_mesh_step`` and the
selector and judge from ``launch.train._components(args,
host_oracle=False)``, built from the workload's flags. ``run_mesh_engine``
has no round-at-a-time entry, so ``step`` repeats its per-step host work
in its order: select, gather the silos' rows, ``jnp.asarray``, the step,
the mask readback, ``selector.update``, the step's scalar readbacks. Each
is inside a span of the benchmark's own (``select``, ``stage_tokens``,
``step``, ``readback``).

Weights are the benchmark's: made on the device from the seed, in one
jitted call, in the tree ``model.init`` describes.
"""
from __future__ import annotations

import contextlib
import gc

import numpy as np

from .. import harness, traffic
from ..reference import fl as ref_fl
from ..reference import qwen3 as ref_lm

NUMBERS = ("selection_mismatch", "mask_mismatch", "loss_gap",
           "client_loss_gap", "entropy_gap", "grad_norm_gap",
           "param_change_gap")
CHECK_STEPS = 3


def train_argv(cfg: dict, tr: dict, seed: int) -> list[str]:
    return ["--arch", cfg["name"], "--engine", "mesh",
            "--clients", str(tr["clients_per_round"]),
            "--logical-clients", str(tr["logical_clients"]),
            "--per-client-batch", str(tr["per_client_batch"]),
            "--seq-len", str(tr["seq_len"]), "--case", tr["case"],
            "--lr", str(tr["lr"]), "--eps", str(tr["eps"]),
            "--seed", str(seed)] + list(cfg.get("program_flags", []))


def _init_leaf(key, i: int, path, s):
    """Leaf ``i`` of the benchmark's weights: unit norm scales,
    N(0, 1/sqrt(fan_in)) matrices, the embedding N(0, 1/sqrt(hidden))."""
    import jax
    import jax.numpy as jnp
    name = jax.tree_util.keystr(path)
    if "norm" in name or "ln" in name or s.ndim == 1:
        return jnp.ones(s.shape, s.dtype)
    fan_in = s.shape[-1] if "embed" in name else s.shape[-2]
    return (jax.random.normal(jax.random.fold_in(key, i), s.shape,
                              jnp.float32) * fan_in ** -0.5).astype(s.dtype)


def init_params(shapes, seed: int):
    """Random weights in the program's tree, on the device, one jitted
    call."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(key, i, p, s)
                      for i, (p, s) in enumerate(leaves)])
    return make(traffic.jax_key(seed))


def change_norms(shapes, seed: int, params) -> dict:
    """Per-leaf norms of ``params``' change from the seed's weights, in one
    jitted call that remakes each initial leaf inside the program, so
    that no copy of the initial weights is held on the device."""
    import jax
    import jax.numpy as jnp
    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def norms(key, params):
        return [jnp.sqrt(jnp.sum(jnp.square(x - _init_leaf(key, i, p, s))))
                for i, ((p, s), x) in enumerate(
                    zip(leaves, jax.tree.leaves(params)))]
    out = norms(traffic.jax_key(seed), params)
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(leaves, out)}


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    return _named(jax.tree.map(float, fn(tree)))


def _named(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


class Driver:
    spans = ("select", "stage_tokens", "step", "readback")

    def __init__(self, cfg: dict, wl: dict, seed: int):
        import jax
        from repro.launch import train
        from repro.launch.mesh import make_host_mesh
        from repro.sharding.ctx import use_mesh
        self.cfg, self.wl, self.seed = cfg, wl, seed
        tr = wl["traffic"]
        self.args = train.parse_args(train_argv(cfg, tr, seed))
        _, self.model = train.build_lm(self.args)
        _, self.selector, judge = train._components(self.args,
                                                    host_oracle=False)
        self.jitted, opt = train.build_mesh_step(self.args, self.model,
                                                 judge)
        self.docs, dom = traffic.token_corpus(cfg["vocab_size"], tr, seed)
        self.sampler = traffic.DocSampler(dom, seed)
        self._shapes = ref_lm.weight_shapes(cfg)
        if jax.eval_shape(self.model.init, jax.random.PRNGKey(0)) != \
                self._shapes:
            raise RuntimeError("the program's weight tree is not the one "
                               "the benchmark makes")
        self.params = init_params(self._shapes, seed)
        self.opt_state = opt.init(self.params)
        self._ctx = contextlib.ExitStack()
        mesh = make_host_mesh()
        self._ctx.enter_context(mesh)
        self._ctx.enter_context(use_mesh(mesh))
        self.m = tr["clients_per_round"]
        rows = self.m * tr["per_client_batch"]
        self.flops = harness.flops_module(cfg["name"]).step_flops(
            cfg, rows, tr["seq_len"] + 1)
        self.round_flops: list[float] = []
        self.records: list[dict] = []
        self._first: dict = {"selected": []}
        self._jax = jax

    def step(self) -> None:
        import jax.numpy as jnp
        ann = self._jax.profiler.TraceAnnotation
        pcb = self.wl["traffic"]["per_client_batch"]
        with ann("select"):
            sel = self.selector.select(self.m)
        with ann("stage_tokens"):
            rows = [self.docs[self.sampler.take(c, pcb)] for c in sel]
            tokens = jnp.asarray(np.concatenate(rows), jnp.int32)
        with ann("step"):
            self.params, self.opt_state, metrics = self.jitted(
                self.params, self.opt_state, {"tokens": tokens})
        with ann("readback"):
            mask = np.asarray(metrics["mask"])
            pos = [sel[i] for i in range(self.m) if mask[i] > 0]
            neg = [sel[i] for i in range(self.m) if mask[i] == 0]
            self.selector.update(pos, neg)
            rec = {k: float(metrics[k]) for k in
                   ("loss", "num_positive", "entropy", "grad_norm")}
        self.round_flops.append(self.flops)
        if len(self.records) < CHECK_STEPS:
            rec.update(mask=mask, client_loss=np.asarray(
                metrics["per_client_loss"], np.float64),
                entropy0=float(metrics["entropy_initial"]))
            self._first["selected"].append(list(sel))
            if not self.records:
                self._first["grad_norms"] = _leaf_norms(
                    self.opt_state["mu"])
            self.records.append(rec)

    def sync(self) -> None:
        self._jax.block_until_ready((self.params, self.opt_state))

    def capture(self) -> dict:
        self.sync()
        recs = self.records[:CHECK_STEPS]
        return dict(self._first,
                    mask=[r["mask"] for r in recs],
                    loss=[r["loss"] for r in recs],
                    client_loss=[r["client_loss"] for r in recs],
                    entropy0=[r["entropy0"] for r in recs],
                    change_norms=change_norms(self._shapes, self.seed,
                                              self.params))

    def close(self) -> None:
        self.params = self.opt_state = self.jitted = None
        self._ctx.close()
        gc.collect()


def _run_reference(cfg, wl, seed, masks, *, dtype="float32", fault=None,
                   steps=CHECK_STEPS) -> dict:
    """The reference through the first steps, on inputs re-drawn from the
    seed: its own pools choose the silos, re-filed by ``masks[t]`` (the
    judged run's) or, where ``masks`` is None, by its own verdicts."""
    import jax
    tr = wl["traffic"]
    docs, dom = traffic.token_corpus(cfg["vocab_size"], tr, seed)
    sampler = traffic.DocSampler(dom, seed)
    pools = ref_fl.Pools(tr["logical_clients"], tr["eps"], seed)
    p0 = init_params(ref_lm.weight_shapes(cfg), seed)
    run = ref_lm.Trainer(cfg, p0, lr=tr["lr"], momentum=tr["momentum"],
                         dtype=dtype, fault=fault)
    out = {"selected": [], "mask": [], "verdict": [], "loss": [],
           "client_loss": [], "entropy0": []}
    for t in range(steps):
        sel = pools.select(tr["clients_per_round"])
        toks = np.stack([docs[sampler.take(c, tr["per_client_batch"])]
                         for c in sel])
        r = run.step(toks, None if masks is None else masks[t])
        mask = r["verdict"] if masks is None else np.asarray(masks[t])
        pools.update([c for c, k in zip(sel, mask) if k > 0],
                     [c for c, k in zip(sel, mask) if k == 0])
        out["selected"].append(sel)
        out["mask"].append(mask)
        for k in ("verdict", "loss", "client_loss", "entropy0"):
            out[k].append(r[k])
    out["grad_norms"] = _named(run.grad_norms)
    out["change_norms"] = _named(run.change_norms(p0))
    del run, p0
    jax.clear_caches()
    return out


def reference_capture(cfg, wl, seed, *, dtype="float32", fault=None,
                      steps=CHECK_STEPS) -> dict:
    """The reference put in the program's place: the same capture."""
    return _run_reference(cfg, wl, seed, None, dtype=dtype, fault=fault,
                          steps=steps)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def compare(cfg, wl, seed, cap: dict) -> dict:
    """Each compared number of a run's capture against the reference,
    which follows the run's masks (checked themselves, exactly)."""
    r = _run_reference(cfg, wl, seed, cap["mask"],
                       steps=len(cap["mask"]))
    sel = sum(int(a != b) for s, t in zip(cap["selected"], r["selected"])
              for a, b in zip(s, t))
    mask = sum(int(np.sum(np.asarray(a) != b))
               for a, b in zip(cap["mask"], r["verdict"]))
    return {
        "selection_mismatch": float(sel),
        "mask_mismatch": float(mask),
        "loss_gap": _rel(cap["loss"], r["loss"]),
        "client_loss_gap": max(_rel(a, b) for a, b in
                               zip(cap["client_loss"], r["client_loss"])),
        "entropy_gap": float(np.max(np.abs(np.subtract(cap["entropy0"],
                                                       r["entropy0"])))),
        "grad_norm_gap": harness.leaf_gap(
            cap["grad_norms"], r["grad_norms"], r["grad_norms"]),
        "param_change_gap": harness.leaf_gap(
            cap["change_norms"], r["change_norms"], r["grad_norms"]),
    }
