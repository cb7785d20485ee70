"""Plain reference of the cross-silo FedEntropy step for a Qwen3 dense
decoder, in straightforward ``jax.numpy``; it imports nothing of the
program.

Model (Qwen3 config.json): token embedding; per layer RMSNorm, attention
with RMSNorm on each head's queries and keys, RoPE, grouped-query
causal softmax attention, residual; RMSNorm, SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), residual; final RMSNorm; logits
against the tied embedding. Departures: RoPE rotates adjacent channel
pairs (2i, 2i+1), the program's layout (see the configuration's
``assumed``); the embedding table may hold more rows than the
vocabulary, and only the first ``vocab_size`` rows are read.

Step (the gradient-level FedEntropy round, E=1): each silo's rows give
its mean next-token loss and its soft label, the mean softmax over every
position of its rows (paper Eq. 2); Alg. 1 (``reference.fl.judge``,
float64, unit sizes) keeps a set of silos; the loss is the mean of the
kept silos' losses; SGD with momentum: ``mu = momentum * mu + grad``,
``params -= lr * mu``.

It runs a silo at a time, so that it fits beside nothing else on one
chip: a forward pass per silo for the verdict, then a gradient per kept
silo, accumulated.

Weights use the program's tree: ``tok.embed``, ``final_norm.scale`` and
per-layer stacks under ``layers`` (``ln1``, ``ln2``, ``attn.{w_q,w_k,
w_v,w_o}.w``, ``attn.{q_norm,k_norm}``, ``mlp.{w_in,w_gate,w_out}.w``).
"""
from __future__ import annotations

import numpy as np

from . import fl as ref_fl


def _rms(x, scale, eps):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    import jax.numpy as jnp
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s)[:, None] * inv[None, :]              # (S, hd/2)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def logits(cfg: dict, p: dict, tokens):
    """(B, S) tokens -> (B, S, vocab) logits."""
    import jax
    import jax.numpy as jnp
    eps = cfg["rms_norm_eps"]
    h_, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    emb = p["tok"]["embed"][: cfg["vocab_size"]]
    x = emb[tokens]
    b, s = tokens.shape
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"]["scale"], eps)
        q = (h @ a["w_q"]["w"]).reshape(b, s, h_, hd)
        k = (h @ a["w_k"]["w"]).reshape(b, s, kv, hd)
        v = (h @ a["w_v"]["w"]).reshape(b, s, kv, hd)
        q = _rope(_rms(q, a["q_norm"], eps), cfg["rope_theta"])
        k = _rope(_rms(k, a["k_norm"], eps), cfg["rope_theta"])
        k = jnp.repeat(k, h_ // kv, axis=2)                 # head i -> i//g
        v = jnp.repeat(v, h_ // kv, axis=2)
        sc = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(hd)
        sc = jnp.where(causal, sc.astype(jnp.float32), -jnp.inf)
        pr = jax.nn.softmax(sc, -1).astype(x.dtype)
        o = jnp.einsum("bhst,bthd->bshd", pr, v).reshape(b, s, h_ * hd)
        x = x + o @ a["w_o"]["w"]
        m = lp["mlp"]
        h = _rms(x, lp["ln2"]["scale"], eps)
        x = x + (jax.nn.silu(h @ m["w_gate"]["w"]) * (h @ m["w_in"]["w"])
                 ) @ m["w_out"]["w"]
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rms(x, p["final_norm"]["scale"], eps)
    return x @ emb.T


def silo_stats(cfg: dict, p: dict, tokens):
    """(mean next-token loss, soft label (vocab,)) of one silo's rows."""
    import jax
    import jax.numpy as jnp
    lg = logits(cfg, p, tokens).astype(jnp.float32)
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], -1)
    soft = jnp.mean(jnp.exp(logp), axis=(0, 1))
    return jnp.mean(nll), soft


def weight_shapes(cfg: dict, vocab_multiple: int = 256) -> dict:
    """The weights' tree and float32 shapes; the embedding's rows are the
    vocabulary rounded up to ``vocab_multiple``."""
    import jax
    import jax.numpy as jnp
    d, f, n = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    rows = -(-cfg["vocab_size"] // vocab_multiple) * vocab_multiple
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    w = lambda a, b: {"w": s(n, a, b)}
    return {"tok": {"embed": s(rows, d)}, "final_norm": {"scale": s(d)},
            "layers": {
                "ln1": {"scale": s(n, d)}, "ln2": {"scale": s(n, d)},
                "attn": {"w_q": w(d, h * hd), "w_k": w(d, kv * hd),
                         "w_v": w(d, kv * hd), "w_o": w(h * hd, d),
                         "q_norm": s(n, hd), "k_norm": s(n, hd)},
                "mlp": {"w_in": w(d, f), "w_gate": w(d, f),
                        "w_out": w(f, d)}}}


class Trainer:
    """The step, one at a time from ``p0``: ``step(tokens, follow)`` with
    (M, rows, L+1) tokens, silo-major, and the judged run's (M,) 0/1 mask
    (None: this reference's own verdict), which the update uses; its
    agreement with this reference's verdict is checked apart.

    ``fault``: ``"unchanged"`` (no update), ``"half"`` (each silo keeps
    half its rows, the mean over the rest), ``"altered"`` (silo 0's
    verdict flipped where it is produced)."""

    def __init__(self, cfg: dict, p0: dict, *, lr: float, momentum: float,
                 dtype: str = "float32", fault: str | None = None):
        import jax
        import jax.numpy as jnp
        self.dt = jnp.dtype(dtype)
        self.prec = "highest" if self.dt == jnp.float32 else "default"
        self.fault = fault
        self.stats = jax.jit(lambda p, t: silo_stats(cfg, p, t))
        self.grad = jax.jit(jax.grad(lambda p, t: silo_stats(cfg, p, t)[0]))
        self.axpy = jax.jit(lambda acc, g, a: jax.tree.map(
            lambda x, y: x + a * y.astype(x.dtype), acc, g))

        def sgd(p, mu, g):
            mu = jax.tree.map(lambda m, x: momentum * m + x, mu, g)
            return jax.tree.map(lambda a, b: a - lr * b, p, mu), mu
        self.sgd = jax.jit(sgd)
        self.norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            t))
        self.diff_norms = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
        self.p = jax.tree.map(lambda x: x.astype(self.dt), p0)
        self.mu = jax.tree.map(jnp.zeros_like, self.p)
        self.grad_norms = None
        self._jax = jax

    def step(self, tokens, follow=None) -> dict:
        jax, jnp = self._jax, self._jax.numpy
        toks = np.asarray(tokens)
        if self.fault == "half":
            toks = toks[:, : max(1, toks.shape[1] // 2)]
        m = toks.shape[0]
        with jax.default_matmul_precision(self.prec):
            ls, softs = [], []
            for i in range(m):
                li, si = self.stats(self.p, jnp.asarray(toks[i]))
                ls.append(float(li))
                softs.append(np.asarray(si, np.float64))
            soft = np.stack(softs)
            kept, _, _ = ref_fl.judge(soft, np.ones(m))
            own = np.zeros(m)
            own[kept] = 1.0
            if self.fault == "altered":
                own[0] = 1.0 - own[0]
            mask = own if follow is None else np.asarray(follow, np.float64)
            g = jax.tree.map(jnp.zeros_like, self.p)
            for i in range(m):
                if mask[i] > 0:
                    g = self.axpy(g, self.grad(self.p, jnp.asarray(toks[i])),
                                  jnp.asarray(1.0 / mask.sum(), self.dt))
            if self.grad_norms is None:
                self.grad_norms = jax.tree.map(float, self.norms(g))
            if self.fault != "unchanged":
                self.p, self.mu = self.sgd(self.p, self.mu, g)
        return {"verdict": own, "client_loss": np.asarray(ls),
                "loss": float(np.dot(mask, ls) / mask.sum()),
                "entropy0": ref_fl._entropy(soft.mean(0))}

    def change_norms(self, p0) -> dict:
        return self._jax.tree.map(float, self.diff_norms(self.p, p0))
