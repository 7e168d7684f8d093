"""The serving path's host spans and its host-sync counter.

A tiny ``PagedEngine`` behind the scheduler, run under the profiler, must
leave the phase spans nested as the scheduler and engine run them, on the
profiler's own clock, read back through the benchmark's span reader; the
engine counts every blocking device -> host read; the collector hook
spans a collection and installs once.
"""
import gc
import os
import sys

import jax
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import ContinuousBatchingScheduler, PagedEngine
from repro.serving.trace import _gc_span, install_gc_spans

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "perfbench"))
from harness import host_spans  # noqa: E402
from harness import trace as bench_trace  # noqa: E402

PROMPTS = [
    "the quick brown fox jumps over the lazy dog today",
    "the quick brown fox jumps over the lazy dog and tomorrow",
    "zzz qqq completely unrelated 12345",
    "what is the capital of france and why",
]


@pytest.fixture(scope="module")
def stack():
    cfg = get_config("dialogpt-medium").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(stack, **kw):
    cfg, params = stack
    return PagedEngine(cfg, params, max_batch=2, capacity=128,
                       max_new_tokens=5, block_size=8, **kw)


def _serve(eng, prompts=PROMPTS):
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p) for p in prompts]
    sched.run()
    return reqs


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=bench_trace.profile_options())
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return host_spans.extract(str(tmp_path))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def _parents(spans, child, parent):
    """For each span named ``child``, whether a ``parent`` span holds it."""
    return [any(_inside(c, p) for p in spans if p[0] == parent)
            for c in spans if c[0] == child]


def test_phase_spans_nest_in_the_step(stack, tmp_path):
    eng = _engine(stack)
    _serve(eng, PROMPTS[:1])                 # compile outside the trace
    spans = _traced(tmp_path, lambda: _serve(eng))
    names = {s[0] for s in spans}
    assert {"sched.step", "sched.admit", "sched.finish", "engine.admission",
            "engine.tier_lookup", "engine.chunk", "engine.first_token",
            "engine.wait", "engine.table_update", "engine.decode",
            "engine.emit"} <= names
    # sched.step > engine.admission > engine.first_token > engine.wait
    for child, parent in (("engine.admission", "sched.step"),
                          ("engine.tier_lookup", "engine.admission"),
                          ("engine.chunk", "engine.admission"),
                          ("engine.first_token", "engine.admission"),
                          ("engine.decode", "sched.step"),
                          ("engine.emit", "sched.step"),
                          ("sched.admit", "sched.step"),
                          ("sched.finish", "sched.step")):
        held = _parents(spans, child, parent)
        assert held and all(held), (child, parent)
    waits = [w for w in spans if w[0] == "engine.wait"]
    firsts = [s for s in spans if s[0] == "engine.first_token"]
    decodes = [s for s in spans if s[0] == "engine.decode"]
    assert len(firsts) == len(PROMPTS)
    assert all(any(_inside(w, p) for p in firsts + decodes) for w in waits)
    assert sum(any(_inside(w, f) for f in firsts) for w in waits) == \
        len(firsts)


def test_span_arguments_stay_out_of_the_name(stack, tmp_path):
    eng = _engine(stack)
    _serve(eng, PROMPTS[:1])
    spans = _traced(tmp_path, lambda: _serve(eng, PROMPTS[:2]))
    adm = [s for s in spans if s[0].startswith("engine.admission")]
    assert adm and all(s[0] == "engine.admission" for s in adm)
    assert all("#" not in s[0] and "=" not in s[0] for s in spans)


@pytest.mark.parametrize("mode", ["chunked", "packed", "staged",
                                  "speculative"])
def test_host_syncs_count_every_blocking_read(stack, mode):
    """One read per first token, one per plain decode step, two per
    speculative round (drafts, then the verifier's targets)."""
    kw = ({"speculative": True, "gamma": 2} if mode == "speculative"
          else {"prefill_mode": mode})
    eng = _engine(stack, **kw)
    reqs = _serve(eng)
    assert all(r.outcome == "ok" for r in reqs)
    st = eng.stats
    assert st["host_syncs"] == (st["admissions"]
                                + st["batched_decode_steps"]
                                + 2 * st["spec_rounds"])
    assert st["admissions"] == len(PROMPTS)
    assert (st["spec_rounds"] > 0) == (mode == "speculative")


def test_request_clock_stamps(stack):
    reqs = _serve(_engine(stack, prefill_mode="packed"))
    for r in reqs:
        assert r.enqueue_t > 0 and r.admit_t is not None
        assert r.queue_delay_s is not None and r.queue_delay_s >= 0.0
        assert r.first_token_t is not None and r.first_token_t >= r.admit_t


def test_gc_spans_install_once_and_span_a_collection(tmp_path):
    install_gc_spans()
    install_gc_spans()
    assert gc.callbacks.count(_gc_span) == 1
    spans = _traced(tmp_path, gc.collect)
    assert [s[0] for s in spans].count("gc") >= 1
