"""Plain reference of one FedEntropy round for the paper's CNN fleet.

Written from the paper (arXiv 2205.12038: Alg. 1, Alg. 2, Appendix
Table 5, Sec. 4.1), in straightforward ``jax.numpy`` and numpy; it
imports nothing of the program. The parts:

* ``Pools`` — Alg. 2 lines 4-8/22: epsilon-greedy draw from the positive
  or the negative pool with spill-over, cohort removed for the round and
  re-filed by verdict. numpy's ``default_rng(seed)`` stream, drawn in the
  order the paper's steps take it.
* ``judge`` — Alg. 1 in float64: remove, one at a time, the member whose
  removal raises the size-weighted group entropy most, while it rises by
  more than 1e-6.
* ``client_update`` — ClientUpdate: E epochs of minibatch SGD with
  momentum over the client's own images in their stored order, minibatch
  B, the last minibatch of an epoch holding the remainder. Momentum is
  carried across epochs. No step is taken beyond the client's data.
* ``aggregate`` — Alg. 2 line 21 in float64: the size-weighted mean of
  the positive clients' models.

``dtype`` float32 runs at ``highest`` matmul precision; bfloat16 is the
control (everything in bfloat16). ``fault`` plants one of the faults the
benchmark must catch: ``"unchanged"`` (the round returns the global
model it was given), ``"half"`` (each minibatch loses its second half,
the mean taken over the rest), ``"altered"`` (client 0's soft label is
rolled by one class where it is produced).
"""
from __future__ import annotations

import numpy as np

TOL = 1e-6


# ---------------------------------------------------------------- pools

class Pools:
    def __init__(self, n: int, eps: float, seed: int):
        self.n = n
        self.eps = eps
        self.pos = set(range(n))
        self.neg: set[int] = set()
        self.rng = np.random.default_rng(seed)

    def select(self, num: int) -> list[int]:
        num = min(num, self.n)
        from_pos = self.rng.random() < self.eps
        first, second = ((self.pos, self.neg) if from_pos
                         else (self.neg, self.pos))
        k = min(num, len(first))
        out = [int(c) for c in
               (self.rng.choice(sorted(first), k, replace=False)
                if k else [])]
        if num > k:
            out += [int(c) for c in self.rng.choice(
                sorted(second), min(num - k, len(second)), replace=False)]
        for c in out:
            self.pos.discard(c)
            self.neg.discard(c)
        return out

    def update(self, pos, neg) -> None:
        self.pos.update(int(c) for c in pos)
        self.neg.update(int(c) for c in neg)


# ---------------------------------------------------------------- judge

def _entropy(p: np.ndarray) -> float:
    p = p[p > 0]
    return float(-np.sum(p * np.log(p)))


def _mix(soft, sizes, members) -> np.ndarray:
    w = sizes[members]
    return (w[:, None] * soft[members]).sum(0) / w.sum()


def judge(soft, sizes):
    """Alg. 1: (kept, removed in removal order, final entropy), indices
    relative to the cohort."""
    soft = np.asarray(soft, np.float64)
    sizes = np.asarray(sizes, np.float64)
    kept = list(range(len(sizes)))
    removed: list[int] = []
    ent = _entropy(_mix(soft, sizes, kept))
    while len(kept) > 1:
        best, best_ent = None, ent
        for k in kept:
            e = _entropy(_mix(soft, sizes, [i for i in kept if i != k]))
            if e > best_ent + TOL:
                best, best_ent = k, e
        if best is None:
            break
        kept.remove(best)
        removed.append(best)
        ent = best_ent
    return kept, removed, ent


def aggregate(stacked: dict, sizes, keep_mask) -> dict:
    """Size-weighted mean of the kept clients' models (float64)."""
    w = np.asarray(sizes, np.float64) * np.asarray(keep_mask, np.float64)
    return {k: np.tensordot(w, np.asarray(v, np.float64), axes=1) / w.sum()
            for k, v in stacked.items()}


# ---------------------------------------------------------------- model

def cnn_init(key, cfg: dict):
    """He-normal weights, zero biases, in the flat {"<layer>.<w|b>"}
    naming (see ``to_program``). One jitted call."""
    import jax
    import jax.numpy as jnp
    shapes = cnn_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shp) in enumerate(sorted(shapes.items())):
            if name.endswith(".b"):
                out[name] = jnp.zeros(shp, jnp.float32)
            else:
                fan_in = int(np.prod(shp[:-1]))
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shp) * np.sqrt(2.0 / fan_in)
        return out
    return make(key)


def cnn_shapes(cfg: dict) -> dict:
    k, ch, c1, c2 = (cfg["kernel_size"], cfg["channels"],
                     cfg["conv1_channels"], cfg["conv2_channels"])
    h = ((cfg["image_hw"] - k + 1) // 2 - k + 1) // 2
    f1, f2 = cfg["fc_widths"]
    return {"conv1.w": (k, k, ch, c1), "conv1.b": (c1,),
            "conv2.w": (k, k, c1, c2), "conv2.b": (c2,),
            "fc1.w": (h * h * c2, f1), "fc1.b": (f1,),
            "fc2.w": (f1, f2), "fc2.b": (f2,),
            "fc3.w": (f2, cfg["num_classes"]),
            "fc3.b": (cfg["num_classes"],)}


def to_program(flat: dict) -> dict:
    """{"conv1.w": ...} -> {"conv1": {"w": ...}}, the program's layout."""
    out: dict = {}
    for name, v in flat.items():
        layer, leaf = name.split(".")
        out.setdefault(layer, {})[leaf] = v
    return out


def from_program(tree: dict) -> dict:
    return {f"{layer}.{leaf}": v for layer, d in tree.items()
            for leaf, v in d.items()}


def cnn_logits(p: dict, x):
    """Appendix Table 5: conv5x5(6) relu pool2, conv5x5(16) relu pool2,
    fc 120 relu, fc 84 relu, fc classes. NHWC; flattened (h, w, c)."""
    import jax
    import jax.numpy as jnp

    def conv(x, w, b):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = pool(jax.nn.relu(conv(x, p["conv1.w"], p["conv1.b"])))
    h = pool(jax.nn.relu(conv(h, p["conv2.w"], p["conv2.b"])))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ p["fc1.w"] + p["fc1.b"])
    h = jax.nn.relu(h @ p["fc2.w"] + p["fc2.b"])
    return h @ p["fc3.w"] + p["fc3.b"]


def client_update(p, x, y, n_real, *, epochs, batch, lr, momentum,
                  fault=None):
    """One client's E local epochs; returns (params, soft label)."""
    import jax
    import jax.numpy as jnp
    s = x.shape[0]
    nb = s // batch
    rows = jnp.arange(s)
    w = (rows < n_real).astype(x.dtype)
    if fault == "half":
        w = w * ((rows % batch) < batch // 2).astype(x.dtype)
    xb = x.reshape((nb, batch) + x.shape[1:])
    yb = y.reshape(nb, batch)
    wb = w.reshape(nb, batch)
    steps = (n_real + batch - 1) // batch          # minibatches with data

    def loss(p, bx, by, bw):
        logp = jax.nn.log_softmax(cnn_logits(p, bx), -1)
        nll = -jnp.take_along_axis(logp, by[:, None], -1)[:, 0]
        return jnp.sum(nll * bw) / jnp.sum(bw)

    def step(carry, inp):
        q, m = carry
        bx, by, bw, k = inp
        g = jax.grad(loss)(q, bx, by, bw)
        m2 = jax.tree.map(lambda a, b: momentum * a + b, m, g)
        q2 = jax.tree.map(lambda a, b: a - lr * b, q, m2)
        live = k < steps
        keep = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(live, a, b), new, old)
        return (keep(q2, q), keep(m2, m)), None

    def epoch(carry, _):
        carry, _ = jax.lax.scan(step, carry, (xb, yb, wb, jnp.arange(nb)))
        return carry, None

    m0 = jax.tree.map(jnp.zeros_like, p)
    (p, _), _ = jax.lax.scan(epoch, (p, m0), None, length=epochs)
    probs = jax.nn.softmax(cnn_logits(p, x).astype(jnp.float32), -1)
    valid = (rows < n_real).astype(jnp.float32)
    soft = (valid[:, None] * probs).sum(0) / valid.sum()
    return p, soft


def run_rounds(cfg: dict, params0: dict, cohort_data, follow: list,
               *, seed: int, rounds: int, dtype: str = "float32",
               fault: str | None = None) -> dict:
    """Follow ``rounds`` rounds from ``params0`` (flat naming, host or
    device). ``cohort_data(ids) -> {"x","y","w"}`` gives the clients'
    stacked rows. ``follow[t]`` is the judged run's verdict of round t
    (``(positive ids, negative ids)``, or None to follow its own): the
    reference aggregates and
    re-files the pools by it, so one verdict that rounding could tip does
    not change every round after it; the verdict itself is checked apart.

    Returns, per round, the selection, sizes, soft labels (float64),
    each client's per-leaf norm of its change from the round's global
    model, its own verdict on its own soft labels, and the final
    model."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    prec = "highest" if dt == jnp.float32 else "default"
    pools = Pools(cfg["num_clients"], cfg["eps"], seed)
    m = max(1, int(round(cfg["num_clients"] * cfg["participation"])))
    upd = jax.jit(jax.vmap(
        lambda p, x, y, n: client_update(
            p, x, y, n, epochs=cfg["local_epochs"],
            batch=cfg["batch_size"], lr=cfg["lr"],
            momentum=cfg["momentum"], fault=fault),
        in_axes=(None, 0, 0, 0)))
    g = {k: np.asarray(v, np.float64) for k, v in params0.items()}
    out = {"selected": [], "sizes": [], "soft": [], "verdict": [],
           "judged": [], "client_change": []}
    for t in range(rounds):
        sel = pools.select(m)
        data = cohort_data(sel)
        n_real = np.asarray(data["w"]).sum(1)
        with jax.default_matmul_precision(prec):
            p_new, soft = upd(
                {k: jnp.asarray(v, dt) for k, v in g.items()},
                jnp.asarray(data["x"], dt), jnp.asarray(data["y"]),
                jnp.asarray(n_real, jnp.int32))
        soft = np.asarray(soft, np.float64)
        out["client_change"].append({
            k: np.sqrt(np.sum(np.square(np.asarray(v, np.float64) - g[k]),
                              axis=tuple(range(1, np.ndim(v)))))
            for k, v in p_new.items()})
        if fault == "altered":
            soft[0] = np.roll(soft[0], 1)
        kept, removed, _ = judge(soft, n_real)
        out["selected"].append(sel)
        out["sizes"].append(n_real.astype(np.float64))
        out["soft"].append(soft)
        out["verdict"].append(([sel[i] for i in kept],
                               [sel[i] for i in removed]))
        out["judged"].append((soft, out["sizes"][-1], kept, removed))
        pos, neg = follow[t] if follow[t] is not None else out["verdict"][-1]
        if fault != "unchanged":
            mask = np.isin(np.asarray(sel), pos).astype(np.float64)
            g = aggregate({k: np.asarray(v, np.float32)
                           for k, v in p_new.items()}, n_real, mask)
        pools.update(pos, neg)
    out["params"] = {k: np.asarray(v, np.float32) for k, v in g.items()}
    return out
