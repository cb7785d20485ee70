"""The benchmark's files: BENCHMARK.json against the schema it must keep,
every name resolving to its file, and a cell, configuration, metric and
path added by new files alone."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_testlib import ROOT, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"][:3] == ["python3", "-m", "bench.run"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    # the metrics every later PR keeps; it may add others by new entries
    assert {m["name"] for m in bench["end_to_end"]} >= {
        "rounds_per_s", "peak_hbm_bytes", "setup_s"}
    assert {m["name"] for m in bench["per_layer"]} >= {
        "device_idle_share", "mfu", "round_ms_p95"}


def test_entries_follow_the_naming_rules(bench):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"])
                assert e["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_every_name_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "flops",
                                           f"{c['name']}.py"))
    for w in bench["workloads"]:
        wl, cfg = harness.cell(w["name"])
        assert wl["config"] == w["config"] and w["config"] in configs
        assert wl["chips"] == w["chips"] and wl["why"] == w["why"]
        mod = harness.driver(wl["driver"])
        assert set(wl["limits"]) == set(mod.NUMBERS)
        assert harness.cell_metrics(bench, w["name"], "end_to_end")
        assert harness.cell_metrics(bench, w["name"], "per_layer")
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_flops_config_agree():
    _, cfg = harness.cell("qwen3-0.6b.silo4")
    assert harness.flops_module("qwen3-0.6b").matmul_params(cfg) == \
        cfg["matmul_params"]


TOY_DRIVER = '''
import jax.numpy as jnp
NUMBERS = ("gap",)

class Driver:
    spans = ("toy",)
    def __init__(self, cfg, wl, seed):
        self.x = jnp.full((4,), float(seed % 7))
        self.round_flops = []
    def step(self):
        self.x = self.x * 1.0 + 1.0
        self.round_flops.append(4.0)
    def sync(self):
        self.x.block_until_ready()
    def capture(self):
        return {"x": float(self.x[0])}
    def close(self):
        self.x = None

def compare(cfg, wl, seed, cap):
    return {"gap": abs(cap["x"] - (seed % 7 + 3))}

def reference_capture(cfg, wl, seed, dtype="float32", fault=None):
    return {"x": float(seed % 7 + 3)}
'''

TOY_RUN = '''
import json, sys, time
sys.path.insert(0, "src")
import jax
from bench import harness
class Chip:
    platform = "cpu"; device_kind = "TPU v5 lite"
    def memory_stats(self): return {"peak_bytes_in_use": 1}
bench = harness.benchmark()
bench["workloads"].append({"name": "toy.one", "config": "toy",
                           "traffic": "one", "chips": 1, "why": "test"})
bench["per_layer"].append({"name": "toy_metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "toy", "moves": "rounds_per_s",
                           "workloads": ["toy.one"]})
bench["end_to_end"].append({"name": "toy_rate", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["toy.one"]})
r = harness.run_cell("toy.one", 3, 0.05, False, t0=time.perf_counter(),
                     devices=[Chip()], bench=bench)
print(json.dumps({"r": r, "metric": harness.metric_reader("toy_metric")(
    {"round_returns": [0.0, 1.0]})}))
'''


def test_a_cell_config_metric_and_path_are_new_files_only(tmp_path):
    """A copy of the benchmark gains a toy path, configuration, cell and
    metric by adding files; the harness runs the cell unchanged."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    (root / "bench" / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "source": "test", "reduced": []}))
    (root / "bench" / "workloads" / "toy.one.json").write_text(json.dumps(
        {"name": "toy.one", "config": "toy", "driver": "toy", "chips": 1,
         "traffic": {}, "trace_seconds": 0.1, "why": "test",
         "limits": {"gap": 0}}))
    (root / "bench" / "metrics" / "toy_metric.py").write_text(
        "def read(ctx):\n    return 1e3 * ctx['round_returns'][-1]\n")
    (root / "bench" / "metrics" / "toy_rate.py").write_text(
        "def read(ctx):\n    return 2.0 / ctx['window_s']\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", TOY_RUN], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["r"]["correct"] is True
    assert res["r"]["checks"] == {"gap": {"value": 0.0, "limit": 0}}
    assert set(res["r"]["metrics"]) == {"rounds_per_s", "peak_hbm_bytes",
                                        "setup_s", "toy_rate"}
    assert res["r"]["metrics"]["toy_rate"]["value"] > 0
    assert res["metric"] == 1000.0
