"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle share and the breakdown, on the host's clock of the trace.

A chip is a plane named ``/device:TPU:<n>``; its operations are the
events of its ``XLA Ops`` line. Busy time is the union of those events'
intervals inside the window; the idle share is one minus busy over the
window. The window is the benchmark's own ``bench_window`` span on the
host plane. Idle gaps are named by the innermost benchmark span that
was open on the host at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def load(trace_dir: str):
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def host_spans(pd, names) -> list[tuple[float, float, str]]:
    """Events named in ``names`` on every non-device plane."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [ev for ev in _events(line) if ev[2] in names]
    return out


def device_ops(pd) -> dict[str, list[tuple[float, float, str]]]:
    """Per chip plane, its operations' (start, end, name) in time order."""
    out = {}
    for plane in pd.planes:
        if not _DEVICE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs += _events(line)
        out[plane.name] = sorted(evs)
    return out


def busy_and_gaps(ops, lo: float, hi: float):
    """(busy ns inside [lo, hi], idle gaps [(start, end)]) of one chip."""
    busy, gaps, cur = 0.0, [], lo
    for s, e, _ in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(ops) -> dict[str, float]:
    """Each operation's time less that of the operations nested in it (a
    loop's event encloses its body's), summed by name."""
    out: dict[str, float] = {}
    stack: list[list] = []            # [end, name, child time]

    def close(item):
        s_end, s_name, s_child, s_dur = item
        out[s_name] = out.get(s_name, 0.0) + s_dur - s_child
    for s, e, name in ops:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([e, op_name(name), 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def _span_at(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


def reduce(pd, span_names, top: int = 10) -> dict:
    """Busy seconds (mean over chips), window seconds, and the breakdown:
    the operations with most device time (mean over chips) and the
    longest idle gaps of the first chip, by the host span open then."""
    win = host_spans(pd, {WINDOW_SPAN})
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0][0], win[0][1]
    chips = device_ops(pd)
    if not chips:
        raise ValueError("no device plane in the trace")
    busy, per_op, gaps0 = [], {}, None
    for name in sorted(chips):
        ops = [ev for ev in chips[name] if ev[1] > lo and ev[0] < hi]
        b, gaps = busy_and_gaps(ops, lo, hi)
        busy.append(b)
        gaps0 = gaps if gaps0 is None else gaps0
        for op, t in self_times(ops).items():
            per_op[op] = per_op.get(op, 0.0) + t / len(chips)
    spans = host_spans(pd, set(span_names))
    longest = sorted(gaps0, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "chips": len(chips),
        "device_ops": [[op, t * 1e-9] for op, t in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_span_at(spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
    }
