"""The one traffic generator: every cell's inputs, from its workload's
``traffic`` parameters and ``--seed``.

Fleet (image) traffic. The partition fixes how many images of each class
each client holds; it comes from the workload (``partition_seed``), not
from ``--seed``, so that every seed runs the same number of SGD steps.
``--seed`` draws the images, their order within each client, the initial
weights and the selector stream. Images are made on the device in the
stacked client layout the program consumes (``x:(N,S,H,W,C)``,
``y:(N,S)``, ``w:(N,S)``; real rows first, zero rows after), by the model
of the program's ``repro.data.synthetic.make_image_dataset``: each class
is an orthonormal low-frequency template, plus Gaussian noise and a
per-image brightness/contrast jitter. One client's rows depend only on
the seed and the client's id, so the reference regenerates exactly the
clients it needs.

Token traffic. Each domain has its own permutation of a Zipf law over the
whole vocabulary; case1 gives logical client ``i`` the domain
``i % num_domains``. Documents are drawn on the host (a few thousand
rows), and each domain hands out its documents in a seeded order, so no
row repeats until a domain's documents run out.
"""
from __future__ import annotations

import numpy as np

TEMPLATE_SEED = 1234      # the program's make_image_dataset default
NOISE = 0.9


def jax_key(seed: int):
    """A PRNG key from any non-negative seed, including ones past 2**31."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# --------------------------------------------------------------- fleet

def class_counts(cfg: dict, traffic: dict) -> np.ndarray:
    """(N, C) images of each class per client, fixed by the workload."""
    n, c = cfg["num_clients"], cfg["num_classes"]
    per_class = cfg["train_per_class"]
    kind = traffic["partition"]
    if kind == "case1":
        counts = np.zeros((n, c), np.int64)
        owners = np.bincount(np.arange(n) % c, minlength=c)
        for i in range(n):
            counts[i, i % c] = per_class // owners[i % c]
        return counts
    if kind == "dirichlet":
        # the draw order of repro.data.partition.partition_dirichlet, so
        # a partition_seed gives the program's own client sizes: every
        # class pool is shuffled (the shuffles consume the stream), then
        # each class is cut by Dirichlet(beta) proportions
        rng = np.random.default_rng(traffic["partition_seed"])
        for _ in range(1000):
            for _ in range(c):
                rng.shuffle(np.arange(per_class))
            counts = np.zeros((n, c), np.int64)
            for k in range(c):
                props = rng.dirichlet(np.full(n, traffic["beta"]))
                cuts = (np.cumsum(props) * per_class).astype(int)[:-1]
                counts[:, k] = np.diff(np.concatenate(
                    [[0], cuts, [per_class]]))
            if counts.sum(1).min() >= traffic.get("min_samples", 2):
                return counts
        raise RuntimeError("no Dirichlet draw met min_samples")
    raise ValueError(f"unknown partition {kind!r}")


def stacked_rows(cfg: dict, counts: np.ndarray) -> int:
    """Rows per client in the stacked layout: the largest client, rounded
    up to whole minibatches."""
    b = cfg["batch_size"]
    return int(-(-counts.sum(1).max() // b) * b)


def client_labels(counts: np.ndarray, rows: int, seed: int):
    """(N, S) labels and (N, S) 0/1 validity: each client's labels in a
    seeded order, real rows first."""
    n = counts.shape[0]
    labels = np.zeros((n, rows), np.int32)
    valid = np.zeros((n, rows), np.float32)
    rng = np.random.default_rng([int(seed), 1])
    for i in range(n):
        y = np.repeat(np.arange(counts.shape[1]), counts[i])
        labels[i, :len(y)] = rng.permutation(y)
        valid[i, :len(y)] = 1.0
    return labels, valid


def templates(cfg: dict) -> np.ndarray:
    """(C, H, W, ch) orthonormal low-frequency class templates."""
    c, hw, ch = cfg["num_classes"], cfg["image_hw"], cfg["channels"]
    t_rng = np.random.default_rng(TEMPLATE_SEED)
    low = t_rng.normal(size=(c, 4 * 4 * ch))
    q, _ = np.linalg.qr(low.T)
    low = (q.T[:c] * np.sqrt(4 * 4 * ch)).reshape(c, 4, 4, ch)
    reps = hw // 4
    return np.repeat(np.repeat(low, reps, axis=1), reps, axis=2).astype(
        np.float32)


def client_images(key, client, labels, valid, tmpl):
    """One client's (S, H, W, ch) images; zero where ``valid`` is 0."""
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(key, client)
    k1, k2, k3 = jax.random.split(k, 3)
    s = labels.shape[0]
    shape = (s,) + tmpl.shape[1:]
    x = tmpl[labels] + NOISE * jax.random.normal(k1, shape)
    bright = 0.2 * jax.random.normal(k2, (s, 1, 1, 1))
    x = x * (1.0 + bright) + 0.1 * jax.random.normal(k3, (s, 1, 1, 1))
    return x * valid[:, None, None, None]


def fleet_data(cfg: dict, traffic: dict, seed: int):
    """The stacked fleet on the device, made in one jitted call, and the
    host-side (labels, valid, counts) the reference regenerates from."""
    import jax
    import jax.numpy as jnp
    counts = class_counts(cfg, traffic)
    labels, valid = client_labels(counts, stacked_rows(cfg, counts), seed)
    tmpl = jnp.asarray(templates(cfg))

    @jax.jit
    def make(key, labels, valid):
        # a few clients at a time, so that the generator's temporaries
        # stay small beside the corpus it writes
        ids = jnp.arange(labels.shape[0])
        x = jax.lax.map(lambda a: client_images(key, *a, tmpl),
                        (ids, labels, valid), batch_size=4)
        return {"x": x, "y": labels, "w": valid}
    data = make(jax_key(seed), labels, valid)
    return data, {"labels": labels, "valid": valid, "counts": counts}


def some_clients(cfg: dict, seed: int, host: dict, ids) -> dict:
    """The stacked rows of the clients ``ids`` alone, as
    :func:`fleet_data` made them."""
    import jax
    import jax.numpy as jnp
    ids = np.asarray(ids)
    tmpl = jnp.asarray(templates(cfg))
    labels = jnp.asarray(host["labels"][ids])
    valid = jnp.asarray(host["valid"][ids])
    x = jax.jit(jax.vmap(client_images, in_axes=(None, 0, 0, 0, None)))(
        jax_key(seed), jnp.asarray(ids), labels, valid, tmpl)
    return {"x": x, "y": labels, "w": valid}


# --------------------------------------------------------------- tokens

def token_corpus(vocab: int, traffic: dict, seed: int):
    """(docs (D, L+1) int32, domain of each doc (D,))."""
    rng = np.random.default_rng([int(seed), 2])
    n_dom = max(4, traffic["logical_clients"] // 2)
    per = traffic["docs_per_domain"]
    width = traffic["seq_len"] + 1
    p = 1.0 / (1.0 + np.arange(vocab, dtype=np.float64)) ** \
        traffic["zipf_exponent"]
    cdf = np.cumsum(p / p.sum())
    docs, dom = [], []
    for d in range(n_dom):
        ranks = rng.permutation(vocab).astype(np.int32)
        u = rng.random((per, width))
        docs.append(ranks[np.minimum(np.searchsorted(cdf, u), vocab - 1)])
        dom.append(np.full(per, d, np.int32))
    return np.concatenate(docs), np.concatenate(dom)


class DocSampler:
    """Each domain hands out its documents in a seeded order (no row
    repeats until the domain runs out, then a fresh order)."""

    def __init__(self, domains: np.ndarray, seed: int):
        self._rng = np.random.default_rng([int(seed), 3])
        self.n_dom = int(domains.max()) + 1
        self._docs = [np.where(domains == d)[0] for d in range(self.n_dom)]
        self._order = [self._rng.permutation(x) for x in self._docs]
        self._pos = [0] * self.n_dom

    def take(self, client: int, k: int) -> np.ndarray:
        d = client % self.n_dom          # case1: one domain per client
        if self._pos[d] + k > len(self._order[d]):
            self._order[d] = self._rng.permutation(self._docs[d])
            self._pos[d] = 0
        out = self._order[d][self._pos[d]: self._pos[d] + k]
        self._pos[d] += k
        return out
