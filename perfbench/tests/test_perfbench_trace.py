"""The reduction from trace events to busy time, idle gaps and device time
by operation and program: a hand-made trace worked by hand, and a slice
of a trace recorded on a TPU v5e."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace  # noqa: E402


def _load(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_base_names_drop_xla_numbers():
    assert trace.base_name("fusion.12") == "fusion"
    assert trace.base_name("jit__paged_step(7)") == "jit__paged_step"
    assert trace.base_name("copy-done.3") == "copy-done"
    assert trace.base_name("paged_decode_attention") == \
        "paged_decode_attention"


def test_hand_worked_trace():
    r = trace.reduce(_load("trace_tiny.json"))
    # window [50, 450): ops cover [50,100) and [150,300) and [380,420)
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(240e-9)
    assert r["op_s"]["paged_decode_attention"] == pytest.approx(140e-9)
    # the loop's time is all its body's: no self time of its own
    assert r["op_s"]["while"] == 0
    assert r["op_s"]["copy"] == pytest.approx(50e-9)
    assert r["op_n"]["paged_decode_attention"] == 2
    assert r["op_s"]["fusion"] == pytest.approx(50e-9)   # clipped at 50
    assert "fusion.9" not in r["op_s"] and r["op_n"]["fusion"] == 1
    assert r["module_s"]["jit__paged_step"] == pytest.approx(190e-9)
    assert r["module_n"]["jit__paged_step"] == 2
    gaps = dict(r["idle_gaps"])
    # [100,150) starts in the first step, after set_row; [300,380) starts
    # between the steps; [420,450) starts in the second step
    assert gaps["bench:step after jit__set_row"] == pytest.approx(50e-9)
    assert gaps["no_span after jit__paged_step"] == pytest.approx(80e-9)
    assert gaps["bench:step after jit__paged_step"] == pytest.approx(30e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_a_window_span_is_required():
    tr = _load("trace_tiny.json")
    tr["spans"] = [s for s in tr["spans"] if s[0] != "bench:window"]
    with pytest.raises(ValueError):
        trace.reduce(tr)


def test_recorded_v5e_trace():
    tr = _load("trace_v5e.json")
    r = trace.reduce(tr)
    assert 0 < r["busy_s"] <= r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert len(gaps) <= 10
    assert sum(gaps.values()) <= r["window_s"] - r["busy_s"] + 1e-9
    # the scanned layer loop holds the kernels: self times keep the
    # kernels' seconds and leave the loop only its own
    ops = dict(r["device_ops"])
    assert r["device_ops"][0][0] == "paged_decode_attention"
    assert ops["paged_decode_attention"] < r["busy_s"]
    assert r["op_s"].get("while", 0.0) < 0.01
    assert r["op_n"]["paged_decode_attention"] == 24      # one per layer
    assert r["module_n"]["jit__paged_step"] == 1
    assert all("%" not in n and "=" not in n for n in r["op_s"])
