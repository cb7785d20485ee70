"""Device time of the held experts' grouped products a traced step, in
ms: the chip's ``XLA Ops`` events of the megablox kernels (``gmm``, the
forward product and the gradient of its rows; ``tgmm``, the gradient of
the experts' weights) or of ``jax.lax.ragged_dot``'s TPU lowering
(``ragged-dot-*``, with its group metadata) inside the window,
the mean over chips, over the program's ``moe.route`` counter spans in
the window (one a step). None where the trace has neither."""
from __future__ import annotations

import functools
import os

from bench import harness, spans
from bench import trace as trace_mod

ROUTE = "moe.route"
GMM_OPS = ("gmm", "tgmm", "ragged-dot")


def _route_stats(event) -> dict:
    """A ``moe.route`` span's keywords: event stats, or the
    ``#key=value,...#`` encoding where a trace keeps them in the name."""
    out = {k: int(v) for k, v in event.stats}
    if "#" in event.name:
        for kv in event.name.split("#")[1].split(","):
            k, _, v = kv.partition("=")
            out[k] = int(v)
    return out


def reduce(pd) -> dict | None:
    """{"routes": [each step's counter keywords], "gmm_ns": device ns of
    the grouped products} in the window; None without a window."""
    win = spans.window(pd)
    if win is None:
        return None
    lo, hi = win
    routes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns
                if spans.base_name(ev.name) == ROUTE and lo <= s and \
                        s + ev.duration_ns <= hi:
                    routes.append(_route_stats(ev))
    chips = trace_mod.device_ops(pd)
    gmm = sum(min(e, hi) - max(s, lo) for ops in chips.values()
              for s, e, name in ops
              if e > lo and s < hi and
              trace_mod.op_name(name).startswith(GMM_OPS))
    return {"routes": routes, "gmm_ns": gmm / max(len(chips), 1)}


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime: float) -> dict | None:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def of_run(ctx: dict) -> dict | None:
    """The reduction of a traced run's trace; None where there is no
    counter span or no grouped product in its window."""
    if not ctx.get("trace"):
        return None
    path = spans.newest(ctx.get("trace_dir") or harness.TRACE_DIR)
    if path is None:
        return None
    r = _reduce_file(path, os.path.getmtime(path))
    if not r or not r["routes"] or not r["gmm_ns"]:
        return None
    return r


def read(ctx: dict):
    r = of_run(ctx)
    return None if r is None else r["gmm_ns"] * 1e-6 / len(r["routes"])
