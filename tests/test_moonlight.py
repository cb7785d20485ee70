"""Moonlight-16B-A3B's block (latent attention, a leading dense layer, the
held-expert layer) against the benchmark's plain reference
(``bench/reference/moonlight.py``), at a small size on the CPU, on seeded
random weights with a nonzero selection bias, under ``highest`` matmul
precision. Latent-cache decode against the full forward is
``test_models.test_decode_matches_forward[moonlight-16b-a3b]``."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.distributed import FedSpec, make_train_step
from repro.kernels import ops, ref as kref
from repro.models import moe as moe_mod
from repro.models.api import build_model
from repro.optim import sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench.reference import moonlight as ref  # noqa: E402

HELD, EP, LAYERS = 2, 2, 3          # 1 dense + 2 expert layers
BIAS_SPREAD = 0.05


def _program_cfg(**kw):
    cfg = ARCHS["moonlight-16b-a3b"].reduced()
    cfg = cfg.replace(num_layers=LAYERS, experts_held=HELD,
                      vocab_size=cfg.vocab_size // EP)
    return cfg.replace(**kw)


def _ref_cfg(cfg, **kw):
    out = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
           "first_k_dense_replace": cfg.first_dense_layers,
           "num_attention_heads": cfg.num_heads,
           "kv_lora_rank": cfg.kv_lora_rank,
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.d_ff,
           "moe_intermediate_size": cfg.moe_d_ff,
           "n_routed_experts": cfg.experts_held,
           "expert_parallel": cfg.num_experts // cfg.experts_held,
           "n_shared_experts": cfg.num_shared_experts,
           "num_experts_per_tok": cfg.experts_per_token,
           "routed_scaling_factor": cfg.routed_scaling,
           "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
           "rope_theta": cfg.rope_theta}
    out.update(kw)
    return out


def _params(cfg, seed=0, spread=BIAS_SPREAD):
    model = build_model(cfg)
    p = model.init(jax.random.PRNGKey(seed))
    bias = p["layers"]["moe"]["router"]["bias"]
    p["layers"]["moe"]["router"]["bias"] = spread * jax.random.normal(
        jax.random.PRNGKey(seed + 1), bias.shape)
    return model, p


def _tokens(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


def test_the_weight_tree_is_the_references():
    cfg = _program_cfg()
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert shapes == ref.weight_shapes(_ref_cfg(cfg))


def test_logits_match_the_reference():
    cfg = _program_cfg()
    model, p = _params(cfg)
    toks = _tokens(cfg, (2, 24))
    with jax.default_matmul_precision("highest"):
        got, aux, stats = model.forward(p, {"tokens": toks})
        want = ref.logits(_ref_cfg(cfg), p, toks)
    # f32 through three layers; the program sums each token's held
    # experts in sorted order, the reference densely: rounding only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert float(aux) == 0.0
    rows = np.asarray(stats["expert_rows"])
    assert rows.shape == (LAYERS - 1, HELD)
    # a token has at most min(k, held) held choices
    assert np.all((rows.sum(1) > 0) &
                  (rows.sum(1) <= 2 * 24 * min(cfg.experts_per_token, HELD)))


def test_zeroing_the_bias_changes_the_selection():
    cfg = _program_cfg()
    _, p = _params(cfg)
    router = jax.tree.map(lambda x: x[0], p["layers"]["moe"]["router"])
    x = jax.random.normal(jax.random.PRNGKey(3), (256, cfg.d_model))
    _, with_bias = moe_mod.route_topk(cfg, router, x)
    _, without = moe_mod.route_topk(cfg, dict(router,
                                              bias=0 * router["bias"]), x)
    changed = np.any(np.sort(np.asarray(with_bias), -1) !=
                     np.sort(np.asarray(without), -1), axis=-1)
    assert 0 < changed.sum() < len(changed)


def test_losses_mask_and_first_gradient_match_the_reference():
    cfg = _program_cfg()
    model, p = _params(cfg)
    m, rows, width = 4, 2, 17
    toks = _tokens(cfg, (m, rows, width), seed=1)
    opt = sgd(lr=0.01, momentum=0.5)
    step = jax.jit(make_train_step(model, opt, FedSpec(num_clients=m)))
    with jax.default_matmul_precision("highest"):
        _, state, metrics = step(p, opt.init(p),
                                 {"tokens": toks.reshape(m * rows, width)})
    run = ref.Trainer(_ref_cfg(cfg), p, lr=0.01, momentum=0.5)
    r = run.step(np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(metrics["mask"]), r["verdict"])
    # f32 losses of the same rows; rounding only
    np.testing.assert_allclose(np.asarray(metrics["per_client_loss"]),
                               r["client_loss"], rtol=1e-5)
    # the momentum after one step is the gradient: per leaf against the
    # reference's largest entry, rounding only
    for path, got in jax.tree_util.tree_flatten_with_path(state["mu"])[0]:
        want = run.mu
        for k in path:
            want = want[k.key]
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale, path
    bias_grad = state["mu"]["layers"]["moe"]["router"]["bias"]
    assert float(jnp.max(jnp.abs(bias_grad))) == 0.0   # selection only
    assert int(np.asarray(metrics["expert_rows"]).sum()) > 0


def test_forced_skew_drops_nothing():
    """Every token's first choice is held expert 0: no assignment is
    dropped, and the layer is the reference's."""
    cfg = _program_cfg()
    _, p = _params(cfg)
    m = jax.tree.map(lambda x: x[0], p["layers"]["moe"])
    m["router"]["bias"] = m["router"]["bias"].at[0].set(100.0)
    t = 64
    x = jax.random.normal(jax.random.PRNGKey(4), (2, t // 2, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, rows = moe_mod.moe_held(cfg, m, x)
        want = ref.moe(_ref_cfg(cfg), m, x)
    rows = np.asarray(rows)
    assert rows[0] == t                   # one expert took every token
    _, top = moe_mod.route_topk(cfg, m["router"], x.reshape(t, -1))
    assert rows.sum() == int(np.sum(np.asarray(top) < HELD))
    # f32 rounding only
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """All 8 shares' routed parts, with the shared experts counted once,
    are the reference's uncut layer."""
    e = 8
    cfg = _program_cfg(num_experts=e, experts_held=1)
    full = cfg.replace(experts_held=e)
    _, p = _params(full)
    m = jax.tree.map(lambda x: x[0], p["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        shared = ref._swiglu(m["shared"], x)
        parts = []
        for rank in range(e):
            share = dict(m, **{k: m[k][rank:rank + 1]
                               for k in ("w_in", "w_gate", "w_out")})
            out, _ = moe_mod.moe_held(cfg, share, x, rank)
            parts.append(out - shared)
        want = ref.moe(_ref_cfg(full), m, x)
    # f32 rounding only, over eight partial sums
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend,rows,k,n,sizes", [
    pytest.param("xla", 200, 128, 256, [50, 0, 90], id="xla"),
    pytest.param("pallas", 200, 128, 256, [50, 0, 90], id="pallas"),
    # whole dimensions that are not 128-multiples: tiles (128, 1024, 704)
    pytest.param("pallas", 384, 1024, 704, [130, 0, 160],
                 id="pallas-1024x704"),
    # the Moonlight cell's widths: k in 512-row tiles (gate, up and their
    # weights' gradient), n in 512-column tiles (the rows' gradient)
    pytest.param("pallas", 300, 2048, 1408, [120, 0, 140],
                 id="pallas-2048x1408"),
])
def test_grouped_product_matches_its_oracle(backend, rows, k, n, sizes):
    """``ops.gmm`` (ragged_dot; megablox in the Pallas interpreter, in
    the tiles ``ops.gmm_tiles`` gives its forward and both gradients)
    against ``ref.gmm_reference``, values and gradients, with an empty
    group and rows past the groups."""
    rng = np.random.default_rng(6)
    lhs = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, k, n)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(fn, a, b):
        return jnp.sum(jnp.sin(fn(a, b, sizes)))
    with jax.default_matmul_precision("highest"):
        got = ops.gmm(lhs, rhs, sizes, backend=backend)
        want = kref.gmm_reference(lhs, rhs, sizes)
        g_got = jax.grad(lambda a, b: loss(
            lambda *z: ops.gmm(*z, backend=backend), a, b), (0, 1))(lhs, rhs)
        g_want = jax.grad(lambda a, b: loss(kref.gmm_reference, a, b),
                          (0, 1))(lhs, rhs)
    assert float(jnp.max(jnp.abs(got[int(jnp.sum(sizes)):]))) == 0.0
    # f32 dot products at highest precision: rounding only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-3)


# The Moonlight cell's expert layer: 2,056 tokens x 6 choices in a buffer
# of 12,336 rows, padded to 128-row tiles; gate and up (2048 -> 1408),
# down (1408 -> 2048). Each runs forward, as the rows' gradient (k and n
# swapped) and as the weights' gradient (``tgmm``, contracting the rows).
CELL_ROWS = 12416
VMEM_BUDGET = 12 * 2**20


def _whole_or_128_divisor(tile, dim):
    return tile == dim or (tile % 128 == 0 and dim % tile == 0)


@pytest.mark.parametrize("product", ["gmm", "rows_grad", "tgmm"])
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_grouped_product_tiles_fit_the_cell(product, k, n):
    """The tiles ``ops.gmm_tiles`` gives each of the cell's six product
    shapes are legal for megablox, fit the VMEM budget as that kernel
    holds them, and run a tenth or fewer of the (128, 128, 128) grid."""
    m = CELL_ROWS
    if product == "rows_grad":
        k, n = n, k
    tm, tk, tn = ops.gmm_tiles(m, k, n, 4)
    assert m % tm == 0 and tm % 128 == 0
    assert _whole_or_128_divisor(tk, k) and _whole_or_128_divisor(tn, n)
    # double-buffered lhs, rhs and out tiles, and the f32 accumulator:
    # (tm, tn) in ``gmm``, (tk, tn) in ``tgmm``
    acc = tk * tn if product == "tgmm" else tm * tn
    assert 2 * 4 * (tm * tk + tk * tn + tm * tn) + 4 * acc <= VMEM_BUDGET
    steps = (m // tm) * (k // tk) * (n // tn)
    assert 10 * steps <= (m // 128) * (k // 128) * (n // 128)
    assert ops._gmm_tiling(4) is ops._gmm_tiling(4)   # one static arg
