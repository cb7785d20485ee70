"""Plain reference of the cross-silo FedEntropy step for Moonlight-16B-A3B
(DeepSeek-V3's block with ``q_lora_rank`` null), in straightforward
``jax.numpy``; it imports nothing of the program.

Model (Moonlight config.json, DeepSeek-V3 modeling): token embedding; per
layer RMSNorm, then latent attention: per head ``q = x W_q`` split into
``q_nope`` (128) and ``q_pe`` (64); ``[c, k_pe] = x W_kva`` with ``c`` of
``kv_lora_rank`` and one ``k_pe`` for every head; ``c = RMSNorm(c)``;
``[k_nope, v] = c W_kvb``; RoPE on ``q_pe`` and ``k_pe``; scores
``[q_nope, q_pe] . [k_nope, k_pe] / sqrt(192)``, causal softmax, times
``v``, then ``W_o``; residual. RMSNorm, then the first
``first_k_dense_replace`` layers' SwiGLU MLP (``down(silu(gate(x)) *
up(x))``), the others' expert layer: ``s = sigmoid(x W_r)`` in float32;
the top ``num_experts_per_tok`` of ``s + bias`` (the bias chooses, it
does not weigh); weights ``s`` there over their sum, times
``routed_scaling_factor``; output ``shared(x) + sum over chosen experts
held here of w_e expert_e(x)``, every expert and the shared one a SwiGLU;
residual. Final RMSNorm; logits against the untied head.

The chip's share: the router scores all ``n_routed_experts *
expert_parallel`` experts; this share holds ``n_routed_experts`` of
them, from ``expert_rank * n_routed_experts``, and adds only their part.
The held experts are computed densely on every token and weighted by the
(mostly zero) gate: no sort, no grouped product.

Departures: RoPE rotates adjacent channel pairs (2i, 2i+1), which is
DeepSeek-V3's own interleaved layout (its modeling code de-interleaves
before ``rotate_half``; see the configuration's ``assumed``); the
embedding and head may hold more rows than the vocabulary, and only the
first ``vocab_size`` are read.

Step: as ``reference.qwen3.Trainer`` (a silo at a time, Alg. 1 in
float64, SGD with momentum), with this model's loss; the grouped
gradient sum and the momentum are updated in place.

Weights use the program's tree: ``tok.{embed,head}``, ``final_norm``,
per-layer stacks ``dense_layers`` and ``layers`` of ``ln1``, ``ln2``,
``attn.{w_q,w_kva,w_kvb,w_o}.w``, ``attn.kv_norm``; ``mlp.{w_in,w_gate,
w_out}.w`` (dense) or ``moe.router.{w,bias}``, ``moe.shared.{w_in,
w_gate,w_out}.w`` and ``moe.{w_in,w_gate,w_out}`` (experts held, stacked).
"""
from __future__ import annotations

import numpy as np

from . import qwen3
from .qwen3 import _rms, _rope


def _mla(cfg: dict, a: dict, h):
    import jax
    import jax.numpy as jnp
    b, s, _ = h.shape
    n, nope, rope, vd, r = (cfg["num_attention_heads"],
                            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    q = (h @ a["w_q"]["w"]).reshape(b, s, n, nope + rope)
    q_pe = _rope(q[..., nope:], cfg["rope_theta"])
    ckv = h @ a["w_kva"]["w"]
    c = _rms(ckv[..., :r], a["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(ckv[..., None, r:], cfg["rope_theta"])[:, :, 0]
    kv = (c @ a["w_kvb"]["w"]).reshape(b, s, n, nope + vd)
    sc = (jnp.einsum("bshd,bthd->bhst", q[..., :nope], kv[..., :nope])
          + jnp.einsum("bshd,btd->bhst", q_pe, k_pe)) / np.sqrt(nope + rope)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal, sc.astype(jnp.float32), -jnp.inf)
    pr = jax.nn.softmax(sc, -1).astype(h.dtype)
    o = jnp.einsum("bhst,bthd->bshd", pr, kv[..., nope:])
    return o.reshape(b, s, n * vd) @ a["w_o"]["w"]


def _swiglu(m: dict, h):
    import jax
    return (jax.nn.silu(h @ m["w_gate"]["w"]) * (h @ m["w_in"]["w"])) @ \
        m["w_out"]["w"]


def gates(cfg: dict, router: dict, h):
    """(B, S, D) -> (B, S, every expert) routed weight of each expert,
    zero where not chosen."""
    import jax
    import jax.numpy as jnp
    e = router["w"].shape[-1]
    s = jax.nn.sigmoid(h.astype(jnp.float32) @
                       router["w"].astype(jnp.float32))
    _, top = jax.lax.top_k(s + router["bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, top, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(top, e, dtype=jnp.float32) * w[..., None],
                   axis=-2)


def moe(cfg: dict, m: dict, h, *, shared: bool = True):
    """The held experts' part of the expert layer (plus the shared
    experts where ``shared``), computed densely."""
    import jax
    import jax.numpy as jnp
    held = cfg["n_routed_experts"]
    lo = cfg.get("expert_rank", 0) * held
    g = gates(cfg, m["router"], h)[..., lo: lo + held].astype(h.dtype)
    up = jnp.einsum("bsd,edf->bsef", h, m["w_in"])
    gate = jnp.einsum("bsd,edf->bsef", h, m["w_gate"])
    y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(gate) * up, m["w_out"])
    out = jnp.einsum("bse,bsed->bsd", g, y)
    return out + _swiglu(m["shared"], h) if shared else out


def logits(cfg: dict, p: dict, tokens):
    """(B, S) tokens -> (B, S, vocab) logits."""
    import jax
    eps = cfg["rms_norm_eps"]
    v = cfg["vocab_size"]
    x = p["tok"]["embed"][:v][tokens]

    def layer(x, lp):
        x = x + _mla(cfg, lp["attn"], _rms(x, lp["ln1"]["scale"], eps))
        h = _rms(x, lp["ln2"]["scale"], eps)
        x = x + (_swiglu(lp["mlp"], h) if "mlp" in lp else
                 moe(cfg, lp["moe"], h))
        return x, None

    for stack in ("dense_layers", "layers"):
        x, _ = jax.lax.scan(layer, x, p[stack])
    x = _rms(x, p["final_norm"]["scale"], eps)
    return x @ p["tok"]["head"][:, :v]


def silo_stats(cfg: dict, p: dict, tokens):
    """(mean next-token loss, soft label (vocab,)) of one silo's rows."""
    import jax
    import jax.numpy as jnp
    lg = logits(cfg, p, tokens).astype(jnp.float32)
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], -1)
    return jnp.mean(nll), jnp.mean(jnp.exp(logp), axis=(0, 1))


def weight_shapes(cfg: dict, vocab_multiple: int = 256) -> dict:
    """The weights' tree and float32 shapes; the embedding's and head's
    rows are the vocabulary rounded up to ``vocab_multiple``."""
    import jax
    import jax.numpy as jnp
    d = cfg["hidden_size"]
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    n, nope, rope, vd, r = (cfg["num_attention_heads"],
                            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    rows = -(-cfg["vocab_size"] // vocab_multiple) * vocab_multiple
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    w = lambda *shape: {"w": s(*shape)}

    def attn(k):
        return {"w_q": w(k, d, n * (nope + rope)), "w_kva": w(k, d, r + rope),
                "kv_norm": s(k, r), "w_kvb": w(k, r, n * (nope + vd)),
                "w_o": w(k, n * vd, d)}

    def mlp(k, width):
        return {"w_in": w(k, d, width), "w_gate": w(k, d, width),
                "w_out": w(k, width, d)}

    def norms(k):
        return {"ln1": {"scale": s(k, d)}, "ln2": {"scale": s(k, d)}}
    return {
        "tok": {"embed": s(rows, d), "head": s(d, rows)},
        "final_norm": {"scale": s(d)},
        "dense_layers": dict(norms(nd), attn=attn(nd),
                             mlp=mlp(nd, cfg["intermediate_size"])),
        "layers": dict(norms(nm), attn=attn(nm), moe={
            "router": {"w": s(nm, d, held * cfg["expert_parallel"]),
                       "bias": s(nm, held * cfg["expert_parallel"])},
            "shared": mlp(nm, cfg["n_shared_experts"] * f),
            "w_in": s(nm, held, d, f), "w_gate": s(nm, held, d, f),
            "w_out": s(nm, held, f, d)})}


class Trainer(qwen3.Trainer):
    """``reference.qwen3.Trainer``'s step with this model's loss; see
    there for ``step(tokens, follow)`` and ``fault``."""

    def __init__(self, cfg: dict, p0: dict, **kw):
        super().__init__(cfg, p0, **kw)
        import jax
        momentum, lr = kw["momentum"], kw["lr"]
        self.stats = jax.jit(lambda p, t: silo_stats(cfg, p, t))
        self.grad = jax.jit(jax.grad(lambda p, t: silo_stats(cfg, p, t)[0]))
        # the sum and the momentum in place: a model of this size leaves
        # no room on one chip for a second copy of either
        self.axpy = jax.jit(lambda acc, g, a: jax.tree.map(
            lambda x, y: x + a * y.astype(x.dtype), acc, g),
            donate_argnums=0)

        def sgd(p, mu, g):
            mu = jax.tree.map(lambda m, x: momentum * m + x, mu, g)
            return jax.tree.map(lambda a, b: a - lr * b, p, mu), mu
        self.sgd = jax.jit(sgd, donate_argnums=1)
