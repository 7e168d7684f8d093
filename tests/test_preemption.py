"""Pressure-safe serving (PR 10): preemption with EXACT resume, bounded
backpressure admission, and typed request outcomes.

The core claim is token identity: a greedy request that is preempted
(its rows demoted to the host L2 / its pending admission cancelled) and
later resumed through the warm admission machinery emits EXACTLY the
tokens an uninterrupted run emits — fp and int8 pools, chunked and
packed prefill.  ``BlockPoolExhausted`` never escapes an engine step:
under an undersized pool the engine preempts victims (least-progress
first, latest-deadline tiebreak) instead of failing the step.

The bounded-backpressure surface is data, not exceptions: full queues
shed at submit, expired deadlines shed before claiming blocks, and every
terminal request carries a typed ``RequestOutcome``.
"""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.blockpool import BlockPoolExhausted, PoolSaturated
from repro.core.faults import plan_from_spec
from repro.models import init_params
from repro.serving import PagedEngine
from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                     RequestOutcome)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

PROMPTS = [
    "the quick brown fox jumps over the lazy dog today and tomorrow",
    "what is the capital of france and why is it paris",
    "zzz qqq completely unrelated 12345 something else entirely here",
]


@pytest.fixture(scope="module")
def stack():
    cfg = get_config("dialogpt-medium").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(engine, prompts, **kw):
    sched = ContinuousBatchingScheduler(engine)
    reqs = [sched.submit(p, **kw) for p in prompts]
    sched.run()
    return sched, reqs


def _reference(stack, *, prefill_mode="chunked", kv_quant=False, max_new=8):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                      max_new_tokens=max_new, block_size=8,
                      enable_partial=True, prefill_mode=prefill_mode,
                      kv_quant=kv_quant)
    _, reqs = _run(eng, PROMPTS, admit=True)
    return {p: r.result.text for p, r in zip(PROMPTS, reqs)}


# ---------------------------------------------------------------------------
# overload: undersized pool, every request preempted-or-not must match
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prefill_mode,kv_quant", [
    ("chunked", False), ("packed", False),
    ("chunked", True), ("packed", True)])
def test_overload_token_identity(stack, prefill_mode, kv_quant):
    """An undersized overcommitted pool forces preemptions; all requests
    still complete with tokens identical to an uncapped run, invariants
    intact, and BlockPoolExhausted never escapes a step."""
    cfg, params = stack
    want = _reference(stack, prefill_mode=prefill_mode, kv_quant=kv_quant)
    small = PagedEngine(cfg, params, max_batch=3, capacity=128,
                        max_new_tokens=8, block_size=8, enable_partial=True,
                        prefill_mode=prefill_mode, kv_quant=kv_quant,
                        num_blocks=10, overcommit=True)
    sched, reqs = _run(small, PROMPTS, admit=True)
    small.check_invariants()
    assert small.stats["preemptions"] > 0
    assert sched.stats["preemptions"] > 0
    for p, r in zip(PROMPTS, reqs):
        assert r.outcome == RequestOutcome.OK, (r.outcome, r.error)
        assert r.result.text == want[p], (p, prefill_mode, kv_quant)
    # at least one result records the preemption it survived
    assert any(r.result.preemptions > 0 for r in reqs)


def test_overload_staged_defers_not_fails(stack):
    """The staged (reference) path cannot chunk, so saturation surfaces
    as PoolSaturated — the scheduler defers and retries, it does not
    reject, and every request still completes."""
    cfg, params = stack
    want = _reference(stack, prefill_mode="staged")
    small = PagedEngine(cfg, params, max_batch=3, capacity=128,
                        max_new_tokens=8, block_size=8, enable_partial=True,
                        prefill_mode="staged", num_blocks=12,
                        overcommit=True)
    sched, reqs = _run(small, PROMPTS, admit=True)
    small.check_invariants()
    for p, r in zip(PROMPTS, reqs):
        assert r.outcome == RequestOutcome.OK, (r.outcome, r.error)
        assert r.result.text == want[p], p
    assert sched.stats["admissions_deferred"] >= 0   # surface exists


# ---------------------------------------------------------------------------
# property: preempt at an ARBITRARY step, resume must be token-identical
# ---------------------------------------------------------------------------
_WANT: dict = {}

if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(n=st.integers(min_value=0, max_value=30),
           mode_i=st.integers(min_value=0, max_value=1),
           quant=st.booleans())
    def test_preempt_anywhere_token_identity(stack, n, mode_i, quant):
        """One injected alloc fault at the n-th allocator call preempts
        some request at an arbitrary point in its life (mid-admission or
        mid-decode); the resumed run is token-identical regardless of
        where the axe fell."""
        mode = ("chunked", "packed")[mode_i]
        cfg, params = stack
        key = (mode, quant)
        if key not in _WANT:
            eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                              max_new_tokens=6, block_size=8,
                              enable_partial=True, prefill_mode=mode,
                              kv_quant=quant)
            _, reqs = _run(eng, PROMPTS)
            _WANT[key] = {p: r.result.text for p, r in zip(PROMPTS, reqs)}
        plan = plan_from_spec(0, alloc=(n,))
        eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                          max_new_tokens=6, block_size=8,
                          enable_partial=True, prefill_mode=mode,
                          kv_quant=quant, fault_plan=plan)
        sched, reqs = _run(eng, PROMPTS)
        eng.check_invariants()
        for p, r in zip(PROMPTS, reqs):
            assert r.outcome == RequestOutcome.OK, (r.outcome, r.error)
            assert r.result.text == _WANT[key][p], (p, n, mode, quant)


# ---------------------------------------------------------------------------
# bounded backpressure: typed shed outcomes
# ---------------------------------------------------------------------------
def test_queue_full_sheds_typed(stack):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=4, block_size=8)
    sched = ContinuousBatchingScheduler(eng, queue_limit=2)
    ok = [sched.submit(p) for p in PROMPTS[:2]]
    shed = sched.submit(PROMPTS[2])
    assert shed.outcome == RequestOutcome.SHED_QUEUE_FULL
    assert shed.done and shed.result is None
    assert sched.stats["shed_queue_full"] == 1
    sched.run()
    for r in ok:
        assert r.outcome == RequestOutcome.OK


def test_tenant_queue_limit(stack):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=4, block_size=8)
    sched = ContinuousBatchingScheduler(
        eng, tenant_queue_limits={"flood": 1})
    a = sched.submit(PROMPTS[0], tenant="flood")
    b = sched.submit(PROMPTS[1], tenant="flood")    # over the tenant bound
    c = sched.submit(PROMPTS[2], tenant="calm")     # other tenants unharmed
    assert a.outcome is None and c.outcome is None
    assert b.outcome == RequestOutcome.SHED_QUEUE_FULL
    sched.run()
    assert a.outcome == RequestOutcome.OK
    assert c.outcome == RequestOutcome.OK


def test_deadline_sheds_before_admission(stack):
    """An already-expired deadline is shed at the step boundary BEFORE
    claiming blocks; live deadlines serve normally."""
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=4, block_size=8)
    sched = ContinuousBatchingScheduler(eng)
    dead = sched.submit(PROMPTS[0], deadline_s=0.0)
    live = sched.submit(PROMPTS[1], deadline_s=3600.0)
    time.sleep(0.01)
    sched.run()
    assert dead.outcome == RequestOutcome.SHED_DEADLINE
    assert dead.result is None
    assert live.outcome == RequestOutcome.OK
    assert sched.stats["shed_deadline"] == 1
    assert eng.stats["admissions"] == 1       # the dead one never admitted


def test_permanent_reject_is_errored(stack):
    """A prompt the pool can NEVER hold is a permanent typed reject, not
    a deferral loop."""
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=2, capacity=32,
                      max_new_tokens=4, block_size=8)
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit("word " * 200)
    sched.run()
    assert req.outcome == RequestOutcome.ERRORED
    assert req.error is not None


@pytest.mark.parametrize("fault", ["over_capacity", "engine_error"])
def test_only_capacity_rejects_are_errored(stack, monkeypatch, fault):
    """The scheduler turns exactly the capacity rejection into ERRORED and
    keeps serving; any other error raised inside ``admit_slot`` — even a
    ValueError — propagates out of ``run`` instead of being recorded as
    a finished request."""
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=4, block_size=8)
    sched = ContinuousBatchingScheduler(eng)
    if fault == "over_capacity":
        bad = sched.submit("word " * 40)
        good = sched.submit(PROMPTS[1])
        sched.run()
        assert bad.outcome == RequestOutcome.ERRORED
        assert "capacity" in bad.error
        assert good.outcome == RequestOutcome.OK
        assert sched.stats["rejected"] == 1
        return

    def broken(prompt, **_):
        raise ValueError("tokenizer table corrupt")

    monkeypatch.setattr(eng.tok, "encode", broken)
    req = sched.submit(PROMPTS[1])
    with pytest.raises(ValueError, match="tokenizer table corrupt"):
        sched.run()
    assert req.outcome != RequestOutcome.ERRORED
    assert sched.stats["rejected"] == 0
    assert eng.free_slots() == [0, 1]       # the slot went back


def test_victim_policy_least_progress(stack):
    """Under pressure the victim is the least-progress row (fewest
    emitted tokens), latest deadline breaking ties."""
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                      max_new_tokens=8, block_size=8, num_blocks=64)
    sched = ContinuousBatchingScheduler(eng)
    old = sched.submit(PROMPTS[0], max_new_tokens=32)
    for _ in range(4):              # let the first request build progress
        sched.step()
    young = sched.submit(PROMPTS[1], max_new_tokens=32)
    sched.step()                    # young admits
    assert len(sched.in_flight) == 2
    # force pressure: exhaust the free list, then step until a decode
    # write crosses a block boundary and must alloc under an empty pool
    # — the YOUNG row (least progress) must be the victim
    grabbed = []
    while True:
        try:
            grabbed.append(eng.allocator.alloc())
        except BlockPoolExhausted:
            break
    for _ in range(10):
        sched.step()
        if eng.stats["preemptions"]:
            break
    for b in grabbed:
        eng.allocator.unref(b)
    assert eng.stats["preemptions"] >= 1
    assert old in sched.in_flight.values()      # survivor: the old row
    sched.run()
    eng.check_invariants()
    assert old.outcome == RequestOutcome.OK
    assert young.outcome == RequestOutcome.OK
    assert young.result.preemptions >= 1


def test_deadline_threads_to_engine_victim_choice(stack):
    """Equal-progress victims: the LATEST deadline is sacrificed first,
    so the tightest-SLO row survives.

    Prompt lengths are chosen around block_size 8 (char tokenizer, +1
    BOS): the ALLOCATOR row (61 chars -> 62 positions) crosses into a
    fresh block at write position 64, i.e. on its 3rd emit — BEFORE the
    two victim rows (49 chars -> 50 positions, crossing at 56 on their
    7th emit).  When the allocator hits the drained pool both victims
    have equal progress, so the engine must break the tie by deadline."""
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                      max_new_tokens=8, block_size=8, num_blocks=64)
    sched = ContinuousBatchingScheduler(eng)
    alloc_row = sched.submit("x" * 61, max_new_tokens=16)
    tight = sched.submit("a" * 49, deadline_s=5.0, max_new_tokens=16)
    loose = sched.submit("b" * 49, deadline_s=3600.0, max_new_tokens=16)
    while sched._queue or not sched.in_flight:
        sched.step()
    grabbed = []
    while True:
        try:
            grabbed.append(eng.allocator.alloc())
        except BlockPoolExhausted:
            break
    for _ in range(10):
        sched.step()
        if eng.stats["preemptions"]:
            break
    for b in grabbed:
        eng.allocator.unref(b)
    assert eng.stats["preemptions"] >= 1
    survivors = list(sched.in_flight.values())
    assert tight in survivors           # tightest SLO kept its row
    assert loose not in survivors       # latest deadline was the victim
    sched.run()
    eng.check_invariants()
    assert alloc_row.outcome == RequestOutcome.OK
    assert tight.outcome == RequestOutcome.OK
    assert loose.outcome == RequestOutcome.OK
    assert loose.result.preemptions >= 1


def test_genresult_carries_preemption_counters(stack):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                      max_new_tokens=8, block_size=8, num_blocks=10,
                      overcommit=True)
    _, reqs = _run(eng, PROMPTS)
    total = sum(r.result.preemptions for r in reqs)
    assert total == eng.stats["preemptions"] - eng.stats["preempt_errors"]
    assert (sum(r.result.tokens_recomputed for r in reqs)
            == eng.stats["preempted_tokens_recomputed"])

