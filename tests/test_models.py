"""Per-architecture smoke tests (reduced configs) + decode consistency.

Every assigned arch: instantiate the REDUCED variant (<=2 layers/groups,
d_model<=256, <=4 experts), run one forward + one train step on CPU, assert
output shapes and no NaNs. Decode consistency: prefill + stepwise decode
reproduces the full-sequence forward logits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ASSIGNED
from repro.core.distributed import FedSpec, make_train_step
from repro.models.api import build_model
from repro.optim import sgd


def _batch(cfg, rng, b=2, s=16):
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(b, cfg.num_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, rng, b=4, s=16)

    logits, aux, _ = model.forward(params, batch)
    assert logits.shape == (4, 16, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())

    fed = FedSpec(num_clients=2)
    opt = sgd(lr=0.01, momentum=0.5)
    step = jax.jit(make_train_step(model, opt, fed))
    new_params, opt_state, metrics = step(params, opt.init(params), batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert metrics["mask"].shape == (2,)
    assert 1 <= int(metrics["num_positive"]) <= 2
    # params actually moved
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree.leaves(new_params),
                        jax.tree.leaves(params)))
    assert moved


@pytest.mark.parametrize("arch", ASSIGNED)
def test_decode_matches_forward(arch, rng):
    cfg = ARCHS[arch].reduced()
    if cfg.num_experts:   # avoid capacity-drop nondeterminism in the check
        cfg = cfg.replace(moe_capacity_factor=float(cfg.num_experts) /
                          cfg.experts_per_token)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s0, sd = 2, 12, 3
    batch = _batch(cfg, rng, b=b, s=s0 + sd)
    toks = batch["tokens"]
    extra = {k: v for k, v in batch.items() if k != "tokens"}
    cache_extra = cfg.num_patches if cfg.family == "vlm" else 0

    full_logits, _, _ = model.forward(params, batch)
    logits, cache = model.prefill(
        params, {"tokens": toks[:, :s0], **extra},
        cache_len=s0 + sd + cache_extra)
    errs = [float(jnp.abs(logits[:, -1] - full_logits[:, s0 - 1]).max())]
    for t in range(s0, s0 + sd):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        errs.append(float(jnp.abs(lg[:, 0] - full_logits[:, t]).max()))
    assert max(errs) < 2e-3, f"decode drift {max(errs)}"


def test_sliding_window_ring_buffer_decode(rng):
    """Windowed decode with a ring cache == full-cache windowed attention."""
    cfg = ARCHS["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    w = 8
    b, steps = 1, 20
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, steps)),
                       jnp.int32)

    # reference: full cache, windowed attention
    full_logits, _, _ = model.forward(params, {"tokens": toks}, window=w)

    # ring cache of exactly window size
    cache = model.init_cache(b, w)
    outs = []
    for t in range(steps):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      window=w)
        outs.append(lg[:, 0])
    ring = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full_logits),
                               atol=2e-3)


def test_moe_router_load_balance_aux(rng):
    cfg = ARCHS["qwen3-moe-235b-a22b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, aux, _ = model.forward(params, _batch(cfg, rng))
    # Switch aux loss >= 1 (equality iff perfectly balanced)
    assert float(aux) >= 0.99


def test_vlm_patch_conditioning_changes_logits(rng):
    cfg = ARCHS["internvl2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    l1, _, _ = model.forward(params, batch)
    batch2 = dict(batch, patches=batch["patches"] + 1.0)
    l2, _, _ = model.forward(params, batch2)
    assert float(jnp.abs(l1 - l2).max()) > 1e-4


def test_encdec_frames_conditioning(rng):
    cfg = ARCHS["whisper-large-v3"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    l1, _, _ = model.forward(params, batch)
    l2, _, _ = model.forward(params, dict(batch,
                                       frames=batch["frames"] * 2.0))
    assert float(jnp.abs(l1 - l2).max()) > 1e-4


def test_gradients_flow_everywhere(rng):
    """No dead parameters in the dense reduced model."""
    cfg = ARCHS["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)

    def loss(p):
        return model.loss(p, batch)[0]
    grads = jax.grad(loss)(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert float(jnp.abs(g).max()) > 0, f"dead grad at {path}"
