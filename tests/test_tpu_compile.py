"""The main path's kernels and client program compile for a described
(not attached) TPU v5e chip.

Interpret-mode tests cannot see what Mosaic refuses (scalar VMEM
stores, unaligned slices, over-budget tiles) or a program that does not
fit HBM; compiling for the described chip can, at no chip time. The
kernels are called with ``interpret=False`` because this process's
default backend is the CPU. The topology is described inside a module
fixture, so only the worker that runs this file loads the TPU compiler.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core.strategies import LocalSpec, client_update
from repro.kernels.entropy_judge import entropy_judge_sweep
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_aggregate import masked_weighted_sum
from repro.launch.train import mesh_step_memory
from repro.models import cnn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CNN_PARAMS = 62006          # paper CNN at 32x32x3, 10 classes
QWEN3_06B_PARAMS = 596049920
V5E_PROGRAM_HBM = int(15.75 * 2**30)    # what XLA:TPU gives one program


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache entry written here could not be read back without
    # a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,c", [(10, 10), (8, 151936)])
def test_entropy_judge_compiles(one_chip, m, c):
    args = [_shape(one_chip, s) for s in ((m, c), (m,), (m,))]
    comp = jax.jit(lambda p, z, k: entropy_judge_sweep(
        p, z, k, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in comp.as_text()


@pytest.mark.parametrize("m,p", [(10, CNN_PARAMS), (2, QWEN3_06B_PARAMS)])
def test_masked_weighted_sum_compiles(one_chip, m, p):
    args = [_shape(one_chip, s) for s in ((m, p), (m,))]
    comp = jax.jit(lambda x, w: masked_weighted_sum(
        x, w, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in comp.as_text()


def test_flash_attention_compiles(one_chip):
    """qwen3-0.6b attention widths: 16 query / 8 KV heads, head_dim 128."""
    q = _shape(one_chip, (1, 2048, 16, 128), jnp.bfloat16)
    kv = _shape(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    comp = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, interpret=False)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in comp.as_text()


def test_cnn_client_update_compiles(one_chip):
    """The vmapped ClientUpdate of the paper's round: cohort 10, E=5,
    B=50 over 100 CIFAR-shaped samples per client."""
    cohort, samples = 10, 100
    params = jax.eval_shape(lambda: cnn.init(jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(params)) == CNN_PARAMS
    params = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                          params)
    data = {"x": _shape(one_chip, (cohort, samples, 32, 32, 3)),
            "y": _shape(one_chip, (cohort, samples), jnp.int32),
            "w": _shape(one_chip, (cohort, samples))}
    spec = LocalSpec(epochs=5, batch_size=50)
    fn = jax.vmap(lambda p, d: client_update(cnn.apply, p, d, spec),
                  in_axes=(None, 0))
    comp = jax.jit(fn).lower(params, data).compile()
    out = comp.out_info
    assert out["soft_label"].shape == (cohort, 10)


def _smoke_lm_argv(seq_len):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    argv = list(smoke.LM_ARGV)
    argv[argv.index("--seq-len") + 1] = str(seq_len)
    return argv


@pytest.mark.parametrize("seq_len,fits", [(128, True), (256, False)])
def test_qwen3_mesh_step_memory(one_chip, seq_len, fits):
    """``chip_smoke.py``'s lm step (qwen3-0.6b at published widths, 4
    clients x 2 sequences, f32 params + SGD momentum) fits one v5e at
    seq 128; at seq 256 the compiler refuses it for HBM."""
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    argv = _smoke_lm_argv(seq_len)
    if fits:
        mem = mesh_step_memory(argv, mesh)
        assert mem["argument"] > 4 * QWEN3_06B_PARAMS * 2   # params + mom.
        assert mem["total"] <= V5E_PROGRAM_HBM
    else:
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="RESOURCE_EXHAUSTED"):
            mesh_step_memory(argv, mesh)


def test_moonlight_mesh_step_memory(one_chip, monkeypatch):
    """The Moonlight cell's step (one chip's share at published widths:
    the dense layer and 5 expert layers, 8 of 64 experts, a 20,480-row
    vocabulary; 4 silos x 2 sequences of 257 tokens, f32 params and SGD
    momentum) compiles for one v5e, with the megablox grouped products it
    runs there, within what XLA gives one program."""
    from repro.kernels import ops
    calls = []
    megablox = ops._megablox_gmm
    monkeypatch.setattr(ops, "_megablox_gmm",
                        lambda *a: calls.append(1) or megablox(*a))
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    mem = mesh_step_memory(
        ["--arch", "moonlight-16b-a3b", "--clients", "4",
         "--per-client-batch", "2", "--seq-len", "256",
         "--expert-parallel", "8", "--num-layers", "6"], mesh)
    assert len(calls) == 3          # gate, up, down of the scanned layer
    assert mem["argument"] > 4 * 668_860_416 * 2     # params + momentum
    assert mem["total"] <= V5E_PROGRAM_HBM
