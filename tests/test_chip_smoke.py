"""``chip_smoke.py`` at CPU scale: its phases run small (Pallas kernels in
interpret mode), ``main`` refuses any device that is not a TPU, and the
compile-cache helper picks its directory from outside the program."""
import importlib.util
import json
import os

import jax
import pytest

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_FLEET = dict(num_clients=8, num_classes=4, train_per_class=20, hw=16,
                  participation=0.5, epochs=1, batch_size=10, rounds=2)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_phase_engines_agree(smoke):
    note = smoke.phase_fleet(**TINY_FLEET)
    assert note.startswith("ints equal over 2 rounds")


def test_fleet_sharded_phase_one_device_mesh(smoke):
    note = smoke.phase_fleet_sharded(chips=1, **TINY_FLEET)
    assert "on 1 chips" in note


def test_kernels_phase_matches_oracles(smoke):
    note = smoke.phase_kernels(judge_shapes=((4, 10), (3, 700)),
                               agg_clients=3, hw=16)
    assert "judge(3,700)" in note and "fused_aggregate(3," in note


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_non_tpu(smoke, capsys, argv):
    assert smoke.main(argv) == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(last)
    assert out["ok"] is False
    assert out["device"]["platform"] == jax.devices()[0].platform


@pytest.mark.parametrize("env", [None, "/srv/jax-cache"])
def test_compile_cache_placement(monkeypatch, env):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    else:
        monkeypatch.setenv(compile_cache.ENV, env)
        want = env
    assert compile_cache.compile_cache_dir() == want
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.use_compile_cache() == want
        # set: JAX reads the variable itself, the helper leaves it alone
        assert jax.config.jax_compilation_cache_dir == (
            prev if env else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_checkout_cache_is_gitignored():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
