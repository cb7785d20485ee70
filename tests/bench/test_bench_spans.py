"""The reduction of the program's spans (``bench/spans.py``) and the seven
per-layer readers on it: on two case1 rounds traced on a TPU v5e with the
program's spans, and on the earlier recording, which has none."""
from __future__ import annotations

import os

import pytest

from bench_testlib import harness
from bench import spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = os.path.join(DATA, "v5e_case1_spans")
METRICS = ("select_host_ms", "stage_host_ms", "readback_wait_ms",
           "host_syncs_per_round", "judge_host_ms", "aggregate_host_ms",
           "client_compute_ms")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(SPANS)


def _ctx(trace_dir):
    return {"trace": {"window_s": 1.0}, "trace_dir": trace_dir}


def test_every_reader_reads_the_recorded_rounds():
    ctx = _ctx(SPANS)
    values = {m: harness.metric_reader(m)(ctx) for m in METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["host_syncs_per_round"] == 2
    # the host waits on the device's answer longer than it selects
    assert values["readback_wait_ms"] > values["select_host_ms"]


def test_no_program_spans_reads_nothing():
    """The earlier recording's program opened no span and named its client
    program otherwise: every reader returns None, none raises."""
    ctx = _ctx(os.path.join(DATA, "v5e_case1"))
    assert {m: harness.metric_reader(m)(ctx) for m in METRICS} == \
        dict.fromkeys(METRICS)


def test_client_compute_is_the_device_time_of_its_ops(recorded):
    """The ``jit_client_update`` modules' time against the union of the
    chip's operations inside them (``bench.trace``), per round."""
    s = spans.summary(recorded)
    lo, hi = spans.window(recorded)
    ops = next(iter(trace.device_ops(recorded).values()))
    busy = 0.0
    for plane in recorded.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != spans.MODULES_LINE:
                continue
            for ev in line.events:
                if ev.name.startswith("jit_client_update") and \
                        lo <= ev.start_ns < hi:
                    end = ev.start_ns + ev.duration_ns
                    busy += trace.busy_and_gaps(
                        [o for o in ops if o[1] > ev.start_ns
                         and o[0] < end], ev.start_ns, end)[0]
    want = busy * 1e-6 / s["rounds"]
    got = harness.metric_reader("client_compute_ms")(_ctx(SPANS))
    assert got == pytest.approx(want, rel=0.1)


def test_the_spans_cover_the_round(recorded):
    s = spans.summary(recorded)
    assert s["rounds"] == 2
    assert s["count"]["fl.fetch"] == 4
    children = sum(v for k, v in s["self_ms"].items() if k != spans.ROUND)
    assert children >= 0.95 * s["total_ms"][spans.ROUND]


def test_idle_by_span_adds_up_to_the_idle_time(recorded):
    r = trace.reduce(recorded, ())
    idle = spans.idle_by_span(recorded)
    assert sum(idle.values()) * 1e-9 == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert set(idle) <= {"none", *spans.NAMES}


class _Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Named:
    def __init__(self, name, items, key):
        self.name = name
        setattr(self, key, items)


def test_self_times_by_hand():
    """By hand: two rounds on one thread inside ``bench_window``, one with
    a keyword-encoded name; self times leave out the nested spans, and
    spans outside the window or of other names are not read. Without the
    window span there is nothing to read."""
    evs = [_Ev("fl.round#round=0#", 100, 200), _Ev("fl.select", 110, 120),
           _Ev("fl.fetch", 130, 190), _Ev("fl.round", 300, 360),
           _Ev("fl.fetch", 300, 350), _Ev("other", 0, 1000),
           _Ev("fl.round", 500, 600)]

    def trace_of(events):
        return _Named("", [_Named("/host:CPU", [_Named("main", events,
                                                       "events")],
                                  "lines")], "planes")

    pd = trace_of(evs + [_Ev(trace.WINDOW_SPAN, 90, 400)])
    assert spans.window(pd) == (90, 400)
    s = spans.summary(pd)
    assert s["rounds"] == 2 and s["count"]["fl.fetch"] == 2
    assert s["self_ms"] == pytest.approx(
        {"fl.round": 40e-6, "fl.select": 10e-6, "fl.fetch": 110e-6})
    assert s["module_ms"] == {}
    assert spans.window(trace_of(evs)) is None
    assert spans.summary(trace_of(evs)) is None


def test_the_command_prints_the_reduction(capsys):
    assert spans.main([SPANS]) == 0
    out = capsys.readouterr().out
    for name in ("fl.fetch", "jit_client_update", "idle on the chip"):
        assert name in out
    assert spans.main([]) == 2
