"""Mesh-level pieces that work on the single real CPU device: sharding
rules, logical axes, param spec coverage, FedSpec ablation, serve steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, ASSIGNED, SHAPES
from repro.core.distributed import (
    FedSpec, cache_logical_axes, chunked_head_stats, make_serve_steps,
    make_train_step, param_logical_axes,
)
from repro.models.api import build_model, input_specs, supported
from repro.optim import sgd
from repro.sharding.specs import logical_to_pspec


class FakeMesh:
    """Just enough of a Mesh for the divisibility rule engine."""
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH_MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_logical_to_pspec_divisibility():
    # kv=2 heads on 16-way model axis -> replicated
    spec = logical_to_pspec(("embed", "kv_heads"), (4096, 2 * 128), MESH)
    assert spec == P("data", "model")          # 256 divides 16
    spec = logical_to_pspec(("embed", "kv_heads"), (4096, 2 * 100), MESH)
    assert spec == P("data", None)             # 200 doesn't divide 16


def test_logical_to_pspec_prefix_fallback():
    # batch=256 on (pod,data)=32 divides fully; batch=8 falls back to the
    # longest dividing prefix (pod=2); batch=1 replicates
    s1 = logical_to_pspec(("batch",), (256,), MESH_MP)
    assert s1 == P(("pod", "data"))
    s2 = logical_to_pspec(("batch",), (8,), MESH_MP)
    assert s2 == P("pod")
    s3 = logical_to_pspec(("batch",), (1,), MESH_MP)
    assert s3 == P(None)


def test_logical_axis_not_reused_across_dims():
    spec = logical_to_pspec(("experts", "embed", "ffn"),
                            (128, 4096, 1536), MESH)
    # experts -> model; ffn would also want model but it's taken
    assert spec == P("model", "data", None)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_logical_axes_cover_all_leaves(arch):
    cfg = ARCHS[arch]
    model = build_model(cfg)
    shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    axes = param_logical_axes(shape)
    flat_s = jax.tree_util.tree_leaves(shape)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_s) == len(flat_a)
    for s, a in zip(flat_s, flat_a):
        assert len(a) == len(s.shape), (s.shape, a)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_big_params_are_sharded(arch):
    """Every leaf > 8 MiB must shard on at least one mesh axis at 16x16."""
    cfg = ARCHS[arch]
    model = build_model(cfg)
    shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    axes = param_logical_axes(shape)

    def check(path, sds, ax):
        nbytes = int(np.prod(sds.shape)) * sds.dtype.itemsize
        if nbytes < 8 * 2**20:
            return
        spec = logical_to_pspec(ax, sds.shape, MESH)
        assert any(p is not None for p in spec), \
            f"{path}: {sds.shape} unsharded"

    for (path, sds), ax in zip(
            jax.tree_util.tree_flatten_with_path(shape)[0],
            jax.tree_util.tree_leaves(
                axes, is_leaf=lambda x: isinstance(x, tuple))):
        check(path, sds, ax)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_build(arch, shape_name):
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    ok, why = supported(cfg, shape)
    if not ok:
        pytest.skip(why)
    specs = input_specs(cfg, shape)
    assert "tokens" in specs
    if shape.kind == "decode":
        assert "cache" in specs
        cache_axes = cache_logical_axes(specs["cache"])
        # structure matches
        jax.tree.map(lambda a, b: None, cache_axes,
                     jax.tree.map(lambda x: None, specs["cache"]),
                     is_leaf=lambda x: isinstance(x, tuple) or x is None)


def test_fedspec_disabled_keeps_all_clients(rng):
    cfg = ARCHS["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)}
    opt = sgd()
    step = make_train_step(model, opt, FedSpec(num_clients=4,
                                               enabled=False))
    _, _, metrics = step(params, opt.init(params), batch)
    assert int(metrics["num_positive"]) == 4


def test_client_sizes_weight_the_loss(rng):
    """Bigger clients pull the aggregate toward their loss (Eq. 4 weights)."""
    cfg = ARCHS["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    opt = sgd()
    step = make_train_step(model, opt, FedSpec(num_clients=2,
                                               enabled=False))
    _, _, m1 = step(params, opt.init(params),
                    {"tokens": toks,
                     "client_sizes": jnp.asarray([1.0, 1.0])})
    _, _, m2 = step(params, opt.init(params),
                    {"tokens": toks,
                     "client_sizes": jnp.asarray([100.0, 1.0])})
    pc = np.asarray(m1["per_client_loss"])
    expect2 = (100 * pc[0] + pc[1]) / 101
    assert float(m2["loss"]) == pytest.approx(expect2, rel=1e-4)


def test_chunked_head_stats_match_dense(rng):
    cfg = ARCHS["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 20)), jnp.int32)
    h, _, _ = model.hidden(params, {"tokens": toks})
    pcl, soft = chunked_head_stats(cfg, params["tok"], h, toks, 2,
                                   seq_chunk=8)
    # dense reference
    from repro.core.distributed import (
        _per_client_loss, per_client_soft_labels)
    logits, _, _ = model.forward(params, {"tokens": toks})
    ref_pcl = _per_client_loss(cfg, logits, toks, 2)
    ref_soft = per_client_soft_labels(logits, 2)
    np.testing.assert_allclose(np.asarray(pcl), np.asarray(ref_pcl),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(soft), np.asarray(ref_soft),
                               atol=1e-6)


def test_serve_steps_roundtrip(rng):
    cfg = ARCHS["mamba2-130m"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prefill_step, decode_step = make_serve_steps(model)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    logits, cache = prefill_step(params, {"tokens": toks})
    lg, cache = decode_step(params, cache,
                            jnp.zeros((2, 1), jnp.int32))
    assert lg.shape == (2, 1, cfg.padded_vocab)
    assert int(cache["index"]) == 9


def test_kv_time_rule_shards_cache():
    """With the kv_time override, a kv-indivisible cache (kv=2 on a 16-way
    model axis) shards its time dim instead of replicating."""
    from repro.core.distributed import cache_logical_axes
    import jax
    leaf = jax.ShapeDtypeStruct((28, 128, 32768, 2, 128), jnp.bfloat16)
    axes = cache_logical_axes({"layers": {"k": leaf}})["layers"]["k"]
    assert axes == (None, "batch", "kv_time", "kv_heads", None)
    # default rules: kv_time unmapped -> replicated time dim
    spec = logical_to_pspec(axes, leaf.shape, MESH)
    assert spec == P(None, "data", None, None, None)
    # override: time -> model
    rules = dict(__import__("repro.sharding.specs",
                            fromlist=["DEFAULT_RULES"]).DEFAULT_RULES)
    rules["kv_time"] = ("model",)
    spec = logical_to_pspec(axes, leaf.shape, MESH, rules)
    assert spec == P(None, "data", "model", None, None)


def test_microbatched_step_matches_full_batch(rng):
    """Two-phase microbatched FedEntropy round (paper stage-1/stage-2 made
    literal) must produce identical masks and updates to the fused step."""
    from repro.core.distributed import make_microbatched_train_step
    cfg = ARCHS["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    m, per, s = 4, 4, 16
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (m * per, s)), jnp.int32)}
    opt = sgd(lr=1.0, momentum=0.0)
    fed = FedSpec(num_clients=m)
    p1, _, m1 = make_train_step(model, opt, fed)(
        params, opt.init(params), batch)
    p2, _, m2 = make_microbatched_train_step(model, opt, fed, 2)(
        params, opt.init(params), batch)
    np.testing.assert_array_equal(np.asarray(m1["mask"]),
                                  np.asarray(m2["mask"]))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-6)
