"""One run of one cell: set-up, the measured window, the traced window,
the check, and the result line.

Everything is found by name: the cell in ``bench/workloads/<cell>.json``,
its configuration in ``bench/configs/<config>.json``, its path in
``bench/drivers/<driver>.py``, each per-layer metric (and any end-to-end
metric beyond ``rounds_per_s``, ``peak_hbm_bytes`` and ``setup_s``) in
``bench/metrics/<metric>.py``, and which metrics a cell reports in
``BENCHMARK.json``. A driver module has a ``Driver(cfg, wl, seed)`` with
``step()``, ``sync()``, ``capture()``, ``close()``, ``spans`` and
``round_flops``, and the functions ``compare(cfg, wl, seed, capture)``
and ``reference_capture(cfg, wl, seed, dtype=, fault=)``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

from . import peaks as peaks_mod
from . import trace as trace_mod

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
CHECK_STEPS = 3


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> tuple[dict, dict]:
    """(workload, configuration) of the cell ``name``."""
    wl = _json(os.path.join(BENCH_DIR, "workloads", f"{name}.json"))
    cfg = _json(os.path.join(BENCH_DIR, "configs", f"{wl['config']}.json"))
    return wl, cfg


def driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def flops_module(config: str):
    """``bench/flops/<config>.py``: the configuration's FLOPs from shapes."""
    path = os.path.join(BENCH_DIR, "flops", f"{config}.py")
    spec = importlib.util.spec_from_file_location(f"bench_flops_{config}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def leaf_gap(got: dict, want: dict, rule: dict) -> float:
    """Worst leaf's gap between two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger; leaves whose
    ``rule`` norm is under a thousandth of the median's are left out."""
    med_rule = float(np.median(list(rule.values())))
    med = float(np.median(list(want.values())))
    gaps = [abs(got[k] - want[k]) / max(want[k], med)
            for k in want if rule[k] >= 1e-3 * med_rule]
    return float(max(gaps))


def metric_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if name in m.get("workloads", [name])]


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, every program kept (no minimum time or size, no eviction:
    with eviction on, one entry without its access-time file makes every
    later write fail)."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts XLA compilations while ``on`` (jax.monitoring events)."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if self.on and name.endswith("backend_compile_duration"):
            self.count += 1


def memory_peak(devices) -> tuple[int, list[dict]]:
    """The largest, over ``devices``, of the runtime's peak of allocated
    buffers plus its peak of memory reserved for programs' temporaries
    (which ``peak_bytes_in_use`` alone leaves out), and every device's
    ``memory_stats()``."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    return max(peaks), stats


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             t0: float, devices, bench: dict | None = None,
             wl: dict | None = None, cfg: dict | None = None) -> dict:
    """One run; returns the result object. ``wl``/``cfg`` default to the
    cell's files (tests pass small ones)."""
    import jax
    bench = benchmark() if bench is None else bench
    if wl is None or cfg is None:
        wl, cfg = cell(name)
    pk = peaks_mod.peaks(devices[0].device_kind)
    mod = driver(wl["driver"])
    counter = CompileCounter()

    drv = mod.Driver(cfg, wl, seed)
    for _ in range(CHECK_STEPS):            # compile, and the checked steps
        drv.step()
    cap = drv.capture()
    setup_s = time.perf_counter() - t0

    counter.on = True
    returns = []
    start = time.perf_counter()
    while True:
        drv.step()
        returns.append(time.perf_counter())
        if returns[-1] - start >= seconds:
            break
    drv.sync()
    window_s = time.perf_counter() - start
    counter.on = False
    rounds = len(returns)
    compiles = counter.count

    ctx = {"round_returns": [start] + returns, "chips": len(devices),
           "peaks": pk}
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        drv.sync()
        before = len(drv.round_flops)
        jax.profiler.start_trace(TRACE_DIR)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            t1 = time.perf_counter()
            while time.perf_counter() - t1 < wl["trace_seconds"]:
                drv.step()
            drv.sync()
        jax.profiler.stop_trace()
        ctx["traced_flops"] = sum(drv.round_flops[before:])
        ctx["trace"] = trace_mod.reduce(trace_mod.load(TRACE_DIR),
                                        drv.spans)

    peak, stats = memory_peak(devices)
    print("memory_stats " + json.dumps(stats), file=sys.stderr)
    drv.close()
    del drv
    t2 = time.perf_counter()
    numbers = mod.compare(cfg, wl, seed, cap)
    print(f"reference check: {time.perf_counter() - t2:.3f} s",
          file=sys.stderr)

    if traced:
        metrics = {}
        for m in cell_metrics(bench, name, "per_layer"):
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        # the three every cell reports; another end-to-end metric is read
        # by its own ``bench/metrics/<metric>.py`` from the window's context
        values = {"rounds_per_s": rounds / window_s, "setup_s": setup_s,
                  "peak_hbm_bytes": peak}
        ctx.update(window_s=window_s, setup_s=setup_s)
        metrics = {}
        for m in cell_metrics(bench, name, "end_to_end"):
            v = (values[m["name"]] if m["name"] in values
                 else metric_reader(m["name"])(ctx))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    checks = {k: {"value": numbers[k], "limit": wl["limits"][k]}
              for k in mod.NUMBERS}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": rounds, "failed": 0, "metrics": metrics,
              "device": device}
    if traced:
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    print(f"window: {rounds} rounds in {window_s:.6f} s; compiles in "
          f"window: {compiles}; setup_s {setup_s:.6f}", file=sys.stderr)
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result
