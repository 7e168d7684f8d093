"""Idle time put down to the program's host spans: a hand-made trace with
nested spans worked by hand, the older fixtures (no program spans), and
the reader of the host-sync counter."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import host_spans, spec, trace  # noqa: E402


def _load(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def _ns(d):
    return {k: round(v * 1e9, 6) for k, v in d.items()}


def _spans_tiny():
    tr = _load("trace_spans_tiny.json")
    return tr, host_spans.idle_by_span(tr, tr["program_spans"])


def test_idle_split_over_the_innermost_span():
    tr, r = _spans_tiny()
    # window [0, 1000); busy [0,100) [300,500) [700,800) [960,980)
    assert r["program_span_count"] == 9
    # gap [100,300) lies in the admission, its first token and the read
    # (which starts with the first token: the shorter span is inner), then
    # straddles the step and the decode dispatch; gap [500,700) starts in
    # the decode read and runs on through emit and finish; gap [800,960)
    # holds a collection, the end of the step, the benchmark's step span
    # and 10 ns of nothing; gap [980,1000) lies under no span
    assert _ns(r["idle_by_span"]) == {
        "engine.admission": 30, "engine.wait": 70,
        "engine.first_token": 70, "sched.step": 90, "engine.decode": 30,
        "engine.emit": 120, "sched.finish": 50, "gc": 40,
        "bench:step": 50, "no_span": 30}


def test_idle_parts_sum_to_window_less_busy():
    tr, r = _spans_tiny()
    red = trace.reduce(tr)
    assert red["window_s"] - red["busy_s"] == pytest.approx(580e-9)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    # the start of each gap names one span for the whole gap
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(580e-9)


def test_idle_under_each_span_and_the_two_host_shares():
    _, r = _spans_tiny()
    under = _ns(r["idle_under_span"])
    assert under["engine.admission"] == 160
    assert under["engine.decode"] == 40        # its own read included
    assert under["sched.step"] == 500 and under["bench:step"] == 550
    assert "no_span" not in under
    # admission 160 ns, the rest of the program's spans 340 ns: both
    # within the 580 ns of idle
    assert host_spans.host_idle_s(r) == pytest.approx((160e-9, 340e-9))


@pytest.mark.parametrize("name", ["trace_tiny.json", "trace_v5e.json"])
def test_older_fixtures_fall_to_the_benchmark_spans(name):
    tr = _load(name)
    r = host_spans.idle_by_span(tr, [])
    red = trace.reduce(tr)
    assert r["program_span_count"] == 0
    assert set(r["idle_by_span"]) <= {"bench:step", "no_span"}
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert host_spans.host_idle_s(r) is None


def test_which_host_spans_are_the_programs():
    assert host_spans.is_program_span("engine.wait")
    assert host_spans.is_program_span("sched.step")
    assert host_spans.is_program_span("gc")
    assert not host_spans.is_program_span("bench:step")
    assert not host_spans.is_program_span("gcx")


def _w(syncs):
    c0 = {"decode_steps": 5, "occupancy_sum": 0}
    c1 = {"decode_steps": 15, "occupancy_sum": 0}
    if syncs is not None:
        c0["engine.host_syncs"], c1["engine.host_syncs"] = syncs
    return SimpleNamespace(counters0=c0, counters1=c1, trace=None,
                           decode_ctx=[], admissions=[], dims=None,
                           peaks=None)


@pytest.mark.parametrize("syncs,want", [((10, 40), 3.0), ((7, 7), 0.0),
                                        (None, None)])
def test_host_syncs_per_step_reader(syncs, want):
    # None: a program without the counter
    got = spec.metric_reader("host_syncs_per_step").read(_w(syncs))
    assert got == (pytest.approx(want) if want is not None else None)
