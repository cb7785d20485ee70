"""The round's profiler spans (``repro.fl.spans``): two rounds of a tiny
FedEntropy server traced on the CPU, read back by the benchmark's trace
reduction and its per-layer readers. CPU numbers: only their presence
and the spans' structure are checked."""
import math
import os
import sys

import jax
import pytest

import repro.fl as fl
from repro.core.strategies import LocalSpec
from repro.data.partition import partition, stack_clients
from repro.data.synthetic import make_image_dataset
from repro.models import cnn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, spans, trace  # noqa: E402

CHILDREN = ["fl.select", "fl.stage", "fl.clients", "fl.fetch", "fl.fetch",
            "fl.judge", "fl.aggregate", "fl.feedback"]
HOST_METRICS = ("select_host_ms", "stage_host_ms", "readback_wait_ms",
                "judge_host_ms", "aggregate_host_ms", "host_syncs_per_round")


def _trace_rounds(d, rounds, **axes):
    """A tiny FedEntropy server (``axes`` override its components): one
    untraced warm-up round, then ``rounds`` rounds inside the benchmark's
    window span under a profiler session writing to ``d``."""
    (xtr, ytr), _ = make_image_dataset(
        num_classes=4, train_per_class=60, test_per_class=15, hw=16,
        noise=0.4, seed=0)
    parts = partition("case1", ytr, 8, 4, seed=0)
    data = stack_clients(xtr, ytr, parts, batch_multiple=20)
    params = cnn.init(jax.random.PRNGKey(0), image_hw=16, num_classes=4)
    server = fl.build("fedentropy", cnn.apply, params, data,
                      fl.ServerConfig(num_clients=8, participation=0.5),
                      LocalSpec(epochs=1, batch_size=20, lr=0.05), **axes)
    server.round()
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(rounds):
                server.round()
    return trace.load(d)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    return d, _trace_rounds(d, 2)


def test_each_round_holds_its_steps_in_order(traced):
    _, pd = traced
    lo, hi = spans.window(pd)
    threads = spans.program_spans(pd, lo, hi)
    rounds = [(s, e) for evs in threads for s, e, n in evs
              if n == "fl.round"]
    assert len(rounds) == 2
    for rs, re_ in rounds:
        inside = [ev for evs in threads for ev in evs
                  if rs <= ev[0] and ev[1] <= re_ and ev[2] != "fl.round"]
        assert [n for _, _, n in inside] == CHILDREN
        # siblings: each ends before the next starts
        for a, b in zip(inside, inside[1:]):
            assert a[1] <= b[0]


def test_speculative_round_holds_the_same_steps(tmp_path):
    """A speculative pipelined round opens the same spans through the
    same round stages. It first judges and aggregates its own cohort on
    the device and folds the strategy state (``fl.judge``,
    ``fl.aggregate``, ``fl.feedback``, all dispatched before any
    readback), then reads the device verdict back (``fl.judge`` with the
    mask and removal-order readbacks inside it); then come ``Server``'s
    steps in ``Server``'s order: the draw, staging and dispatch of the
    next round's cohort, and this round's readbacks, oracle judgment,
    aggregate (the confirmed speculation adopted) and feedback."""
    pd = _trace_rounds(str(tmp_path), 2, engine="pipelined",
                       runtime=fl.RuntimeConfig(speculate=True))
    lo, hi = spans.window(pd)
    threads = spans.program_spans(pd, lo, hi)
    rounds = [(s, e) for evs in threads for s, e, n in evs
              if n == "fl.round"]
    assert len(rounds) == 2
    for rs, re_ in rounds:
        inside = [ev for evs in threads for ev in evs
                  if rs <= ev[0] and ev[1] <= re_ and ev[2] != "fl.round"]
        assert [n for _, _, n in inside] == [
            "fl.judge", "fl.aggregate", "fl.feedback", "fl.judge",
            "fl.fetch", "fl.fetch"] + CHILDREN
        top = [ev for ev in inside
               if not any(o is not ev and o[0] <= ev[0] and ev[1] <= o[1]
                          for o in inside)]
        assert [n for _, _, n in top] == [
            "fl.judge", "fl.aggregate", "fl.feedback", "fl.judge"] + CHILDREN
        for a, b in zip(top, top[1:]):
            assert a[1] <= b[0]


def test_rounds_carry_distinct_numbers(traced):
    _, pd = traced
    numbers = [dict(e.stats).get("round")
               for p in pd.planes for line in p.lines for e in line.events
               if spans.base_name(e.name) == "fl.round"]
    assert len(numbers) == 2 and None not in numbers
    assert len(set(numbers)) == 2


def test_children_cover_the_round(traced):
    _, pd = traced
    s = spans.summary(pd)
    assert s["rounds"] == 2
    children = sum(v for k, v in s["self_ms"].items() if k != "fl.round")
    assert children >= 0.9 * s["total_ms"]["fl.round"]


def test_readers_on_a_cpu_trace(traced):
    d, _ = traced
    ctx = {"trace": {"window_s": 1.0}, "trace_dir": d}
    for name in HOST_METRICS:
        v = harness.metric_reader(name)(ctx)
        assert v is not None and math.isfinite(v) and v >= 0, name
        assert harness.metric_reader(name)({}) is None
    assert harness.metric_reader("host_syncs_per_round")(ctx) == 2
    # no chip plane on the CPU: no device program time
    assert harness.metric_reader("client_compute_ms")(ctx) is None


def test_device_selector_and_judge_readbacks_are_fetches(tmp_path):
    """The traced pool draw and the xla judge read their results back
    through ``spans.fetch``: one ``fl.fetch`` inside ``fl.select`` (the
    draw), three inside ``fl.judge`` (mask, removal order, entropy), and
    the round's own two, all counted by ``host_syncs_per_round``."""
    pd = _trace_rounds(str(tmp_path), 1, selector="pools-traced",
                       judge=fl.MaxEntropyJudge(backend="xla"))
    lo, hi = spans.window(pd)
    evs = [ev for t in spans.program_spans(pd, lo, hi) for ev in t]

    def fetches_in(name):
        (s, e, _), = [ev for ev in evs if ev[2] == name]
        return sum(1 for fs, fe, n in evs
                   if n == "fl.fetch" and s <= fs and fe <= e)

    assert fetches_in("fl.round") == 6
    assert fetches_in("fl.select") == 1
    assert fetches_in("fl.judge") == 3
    ctx = {"trace": {"window_s": 1.0}, "trace_dir": str(tmp_path)}
    assert harness.metric_reader("host_syncs_per_round")(ctx) == 6
