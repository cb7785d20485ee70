"""Path ``lm_mesh_moe``: ``lm_mesh``'s gradient-level FedEntropy step for a
model with held-expert layers (``repro.launch.train``'s mesh engine with
``--expert-parallel``), with ``bench/reference/moonlight.py`` as its
reference.

It is ``lm_mesh`` with four differences. The readback that the step
already makes also copies the step's routing counters (``expert_rows``)
to the host, opens the program's ``moe.route`` counter span from them
(``repro.fl.spans.route_counter``, as ``run_mesh_engine`` does), and
counts the step's FLOPs from them. The router's selection bias is drawn
from the seed at a spread that moves some tokens' choice of experts
(``lm_mesh``'s unit draw would give it a spread of 1/sqrt(layers)). The
reference frees the initial weights once its first update is made, and
the change of the weights is taken against weights remade inside one
jitted call, as the program's is, so that one chip holds it. The initial
group entropy is not compared: its sound gaps swing with the top-6
choices that flip behind the program's one-pass matmuls, as widely as the
bfloat16 control's and a frozen state's (PERF.md, section 4).
"""
from __future__ import annotations

import contextlib

import numpy as np

from .. import harness, traffic
from ..reference import fl as ref_fl
from ..reference import moonlight as ref_lm
from . import lm_mesh
from .lm_mesh import _leaf_norms, _named, _rel, train_argv

NUMBERS = tuple(n for n in lm_mesh.NUMBERS if n != "entropy_gap")
CHECK_STEPS = lm_mesh.CHECK_STEPS
BIAS_SPREAD = 0.05


def _init_leaf(key, i: int, path, s):
    """``lm_mesh``'s leaf ``i``, but the router's selection bias is
    N(0, BIAS_SPREAD)."""
    import jax
    import jax.numpy as jnp
    if jax.tree_util.keystr(path).endswith("['router']['bias']"):
        return (jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32) * BIAS_SPREAD).astype(s.dtype)
    return lm_mesh._init_leaf(key, i, path, s)


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
    """Per-leaf norms of ``params``' change from the seed's weights, each
    initial leaf remade inside one jitted call."""
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


class Driver(lm_mesh.Driver):

    def __init__(self, cfg: dict, wl: dict, seed: int):
        import jax
        from repro.fl.spans import route_counter
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
        fl = harness.flops_module(cfg["name"])
        rows, positions = self.m * tr["per_client_batch"], tr["seq_len"] + 1
        self.flops = lambda routed: fl.step_flops(cfg, rows, positions,
                                                  routed)
        self.route_counter = route_counter
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
            expert_rows = np.asarray(metrics["expert_rows"])
            self.route_counter(expert_rows)
        self.round_flops.append(self.flops(int(expert_rows.sum())))
        if len(self.records) < CHECK_STEPS:
            rec.update(mask=mask, client_loss=np.asarray(
                metrics["per_client_loss"], np.float64))
            self._first["selected"].append(list(sel))
            if not self.records:
                self._first["grad_norms"] = _leaf_norms(
                    self.opt_state["mu"])
            self.records.append(rec)

    def capture(self) -> dict:
        self.sync()
        recs = self.records[:CHECK_STEPS]
        return dict(self._first,
                    mask=[r["mask"] for r in recs],
                    loss=[r["loss"] for r in recs],
                    client_loss=[r["client_loss"] for r in recs],
                    change_norms=change_norms(self._shapes, self.seed,
                                              self.params))


def _run_reference(cfg, wl, seed, masks, *, dtype="float32", fault=None,
                   steps=CHECK_STEPS) -> dict:
    """``lm_mesh._run_reference`` with this reference and weights."""
    import jax
    tr = wl["traffic"]
    docs, dom = traffic.token_corpus(cfg["vocab_size"], tr, seed)
    sampler = traffic.DocSampler(dom, seed)
    pools = ref_fl.Pools(tr["logical_clients"], tr["eps"], seed)
    shapes = ref_lm.weight_shapes(cfg)
    run = ref_lm.Trainer(cfg, init_params(shapes, seed), lr=tr["lr"],
                         momentum=tr["momentum"], dtype=dtype, fault=fault)
    out = {"selected": [], "mask": [], "verdict": [], "loss": [],
           "client_loss": []}
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
        for k in ("verdict", "loss", "client_loss"):
            out[k].append(r[k])
    out["grad_norms"] = _named(run.grad_norms)
    out["change_norms"] = change_norms(shapes, seed, run.p)
    del run
    jax.clear_caches()
    return out


def reference_capture(cfg, wl, seed, *, dtype="float32", fault=None,
                      steps=CHECK_STEPS) -> dict:
    """The reference put in the program's place: the same capture."""
    return _run_reference(cfg, wl, seed, None, dtype=dtype, fault=fault,
                          steps=steps)


def compare(cfg, wl, seed, cap: dict) -> dict:
    """``lm_mesh.compare`` against this reference."""
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
        "grad_norm_gap": harness.leaf_gap(
            cap["grad_norms"], r["grad_norms"], r["grad_norms"]),
        "param_change_gap": harness.leaf_gap(
            cap["change_norms"], r["change_norms"], r["grad_norms"]),
    }
