"""The program's own spans (``fl.*``, ``repro.fl.spans``) and its device
programs in a profiler trace, reduced per traced round.

Inside the benchmark's ``bench_window`` span, each ``fl.*`` span of the
host planes gets its self time (its duration less that of the ``fl.*``
spans nested in it on the same thread), summed by name; the chip's
``XLA Modules`` events are summed by module name (``jit_client_update``),
the mean over chips. A round is an ``fl.round`` span inside the window.
The per-layer readers (``bench/metrics/*.py``) divide by the rounds.

  python3 -m bench.spans <trace_dir>

prints that reduction of the newest ``.xplane.pb`` under ``trace_dir``,
and the chip's idle time in the window, each instant of it put down to
the innermost ``fl.*`` span open then (``none`` outside any). The span
names are the program's own; a program without ``repro.fl.spans`` opens
none, and every reader then returns None.
"""
from __future__ import annotations

import functools
import glob
import os
import sys

from . import harness
from . import trace as trace_mod

if os.path.join(harness.ROOT, "src") not in sys.path:   # as bench.run does
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
try:
    from repro.fl.spans import (AGGREGATE, FETCH, JUDGE, NAMES, ROUND,
                                SELECT, STAGE)
except ModuleNotFoundError:         # a program that opens no spans
    AGGREGATE = FETCH = JUDGE = ROUND = SELECT = STAGE = None
    NAMES = ()

MODULES_LINE = "XLA Modules"


def base_name(name: str) -> str:
    """A span's name without the ``#key=value#`` encoding of its
    keywords, where a trace keeps it in the name."""
    return name.split("#", 1)[0]


def window(pd) -> tuple[float, float] | None:
    """The ``bench_window`` span; None in a trace without one."""
    win = trace_mod.host_spans(pd, {trace_mod.WINDOW_SPAN})
    return (win[0][0], win[0][1]) if win else None


def program_spans(pd, lo: float, hi: float) -> list[list[tuple]]:
    """Per host thread, its ``fl.*`` spans inside [lo, hi] as (start,
    end, name), parents before the spans they hold."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(s, e, base_name(n)) for s, e, n in trace_mod._events(line)
                   if base_name(n) in NAMES and s >= lo and e <= hi]
            if evs:
                out.append(sorted(evs, key=lambda ev: (ev[0], -ev[1])))
    return out


def module_times(pd, lo: float, hi: float) -> dict[str, float]:
    """Device ns of each ``XLA Modules`` program (``jit_x(123)`` ->
    ``jit_x``) that starts inside [lo, hi], the mean over chips."""
    chips = [p for p in pd.planes if trace_mod._DEVICE.match(p.name)]
    out: dict[str, float] = {}
    for plane in chips:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for s, e, name in trace_mod._events(line):
                if lo <= s < hi:
                    key = name.split("(", 1)[0]
                    out[key] = out.get(key, 0.0) + (min(e, hi) - s) / len(
                        chips)
    return out


def summary(pd) -> dict | None:
    """Rounds in the window and, by name, the spans' count, total and
    self ms and the programs' device ms; None without a window."""
    win = window(pd)
    if win is None:
        return None
    count: dict[str, int] = {}
    total: dict[str, float] = {}
    self_ns: dict[str, float] = {}
    for evs in program_spans(pd, *win):
        for s, e, name in evs:
            count[name] = count.get(name, 0) + 1
            total[name] = total.get(name, 0.0) + (e - s) * 1e-6
        for name, t in trace_mod.self_times(evs).items():
            self_ns[name] = self_ns.get(name, 0.0) + t
    return {"rounds": count.get(ROUND, 0), "count": count,
            "total_ms": total,
            "self_ms": {k: v * 1e-6 for k, v in self_ns.items()},
            "module_ms": {k: v * 1e-6
                          for k, v in module_times(pd, *win).items()}}


def newest(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=4)
def _summary_of(path: str, mtime: float) -> dict | None:
    from jax.profiler import ProfileData
    return summary(ProfileData.from_file(path))


def of_run(ctx: dict) -> dict | None:
    """The summary of a traced run's trace (``ctx["trace_dir"]``, else
    the harness's), loaded once per file; None for an untraced run."""
    if not ctx.get("trace"):
        return None
    path = newest(ctx.get("trace_dir") or harness.TRACE_DIR)
    return None if path is None else _summary_of(path,
                                                 os.path.getmtime(path))


def per_round(ctx: dict, kind: str, name: str) -> float | None:
    """``summary[kind][name]`` over the traced rounds; None where the
    trace has no round or no such entry."""
    s = of_run(ctx)
    if not s or not s["rounds"] or name not in s[kind]:
        return None
    return s[kind][name] / s["rounds"]


def idle_by_span(pd) -> dict[str, float]:
    """The first chip's idle ns in the window, each piece of a gap put
    down to the innermost ``fl.*`` span open on the host then."""
    lo, hi = window(pd)
    chips = trace_mod.device_ops(pd)
    ops = [ev for ev in chips[sorted(chips)[0]] if ev[1] > lo and ev[0] < hi]
    _, gaps = trace_mod.busy_and_gaps(ops, lo, hi)
    spans = [ev for evs in program_spans(pd, lo, hi) for ev in evs]
    out: dict[str, float] = {}
    for gs, ge in gaps:
        inside = [ev for ev in spans if ev[0] < ge and ev[1] > gs]
        cuts = sorted({gs, ge} | {t for s, e, _ in inside for t in (s, e)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            name = trace_mod._span_at(inside, (a + b) / 2)
            out[name] = out.get(name, 0.0) + b - a
    return out


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    path = newest(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    s = summary(pd)
    if s is None:
        print(f"no {trace_mod.WINDOW_SPAN!r} span in {path}",
              file=sys.stderr)
        return 1
    n = max(s["rounds"], 1)
    lo, hi = window(pd)
    print(f"{path}\nwindow {(hi - lo) * 1e-6:.3f} ms, {s['rounds']} "
          f"rounds")
    print("\nspan            count/round  self ms/round  total ms/round")
    for name in sorted(s["count"]):
        print(f"{name:<15} {s['count'][name] / n:>11.2f}  "
              f"{s['self_ms'][name] / n:>13.3f}  "
              f"{s['total_ms'][name] / n:>14.3f}")
    print("\nprogram                       device ms/round")
    for name, ms in sorted(s["module_ms"].items(), key=lambda kv: -kv[1]):
        print(f"{name:<29} {ms / n:>15.3f}")
    if trace_mod.device_ops(pd):
        idle = idle_by_span(pd)
        tot = sum(idle.values()) or 1.0
        print(f"\nidle on the chip: {tot * 1e-6:.3f} ms, "
              f"{100 * tot / (hi - lo):.2f}% of the window")
        print("span            idle ms/round  share of idle %")
        for name, t in sorted(idle.items(), key=lambda kv: -kv[1]):
            print(f"{name:<15} {t * 1e-6 / n:>13.3f}  "
                  f"{100 * t / tot:>15.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
