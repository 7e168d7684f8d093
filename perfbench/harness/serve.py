"""One run of a serving cell: build the system under test the way
``repro.launch.serve`` builds it, warm up, serve the mix's closed loop
through a pre-roll, then measure a window.

The entry the window drives is ``ContinuousBatchingScheduler.step`` over
``PagedEngine``: the Pallas kernels on a TPU (``runtime.on_tpu``), the
default chunked admission, a bf16 pool of 16-token pages, rows of the
configuration's context, greedy decoding, no speculation and the
scheduler's defaults.  Requests are not admitted to the host store, so
the device block trie is the recycling tier.

Clock: every stamp is ``time.perf_counter``.  A request is due when its
user sends it (start offset, or the previous reply plus think time); its
first token is the engine's own stamp put on this clock as (moment
``step()`` returned it) - ``latency_s`` + ``ttft_s``.
"""
from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from harness import rows as rows_mod
from harness import traffic

# tokens per warm-up request: the first from the admission, the second
# from one decode step, which writes the first position of a new page
WARMUP_NEW = 2


@dataclass
class Req:
    rid: int
    user: int
    index: int                 # the user's turn number
    due: float
    submit: float
    done: Optional[float] = None
    first: Optional[float] = None
    ok: bool = False
    served: Optional[np.ndarray] = None     # prompt + served token ids
    gen: int = 0               # served tokens


def _record(r: Req, h):
    """Copy what the scheduler's request ``h`` holds at its end into
    ``r``; returns its ``GenResult`` (None when it has none)."""
    res = h.result
    r.ok = res is not None and h.outcome == "ok"
    if res is not None:
        r.gen = res.gen_tokens
        r.served = np.asarray(res.token_ids)
    return res


@dataclass
class CompileLog:
    """JAX's own compile events, stamped on the benchmark's clock."""
    events: List[tuple] = field(default_factory=list)

    def __call__(self, event, duration, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/core/compile/jaxpr_trace_duration"):
            self.events.append((time.perf_counter(), event, duration))

    def count(self, kind: str, t0: float, t1: float) -> int:
        return sum(1 for t, e, _ in self.events
                   if t0 <= t <= t1 and e.endswith(kind))

    def seconds(self, kind: str, t0: float, t1: float) -> float:
        return sum(d for t, e, d in self.events
                   if t0 <= t <= t1 and e.endswith(kind))


class Loop:
    """The mix's users in a closed loop around one scheduler."""

    def __init__(self, sched, mix: dict, rows: int, context: int,
                 seed: int, annotate):
        self.sched = sched
        self.seed = seed
        self.annotate = annotate
        self.plans = traffic.plan(mix, rows, seed)
        self.sessions = [traffic.Session(traffic.personas(mix, seed),
                                         context)
                         for _ in self.plans]
        self.next_turn = [0] * len(self.plans)
        self.waiting: list = []               # heap of (due, user)
        self.reqs: Dict[int, Req] = {}
        self.steps: List[tuple] = []          # (start, end) per step
        self.decode_ctx: List[List[int]] = []  # per step: rows' contexts
        # rid -> (step of the first token, prompt tokens, reused tokens)
        self.admitted: Dict[int, tuple] = {}
        self.plan_exhausted = 0
        self.t0 = None

    def start(self, t0: float) -> None:
        self.t0 = t0
        for u, p in enumerate(self.plans):
            heapq.heappush(self.waiting, (t0 + p.start_s, u))

    # ------------------------------------------------------------------
    def _submit_due(self, now: float) -> None:
        while self.waiting and self.waiting[0][0] <= now:
            due, u = heapq.heappop(self.waiting)
            k = self.next_turn[u]
            if k >= len(self.plans[u].turns):
                self.plan_exhausted += 1
                continue
            self.next_turn[u] = k + 1
            turn = self.plans[u].turns[k]
            text = traffic.user_text(self.seed, u, k, turn.user_tokens)
            prompt = self.sessions[u].prompt(turn, text)
            h = self.sched.submit(prompt, max_new_tokens=turn.reply_tokens,
                                  admit=False)
            self.reqs[h.request_id] = Req(h.request_id, u, k, due,
                                          time.perf_counter())

    def _complete(self, h, t: float) -> None:
        r = self.reqs[h.request_id]
        r.done = t
        res = _record(r, h)
        if res is not None and res.ttft_s > 0.0:
            r.first = t - res.latency_s + res.ttft_s
        self.sessions[r.user].reply(
            r.served[len(r.served) - r.gen:] if res is not None else [])
        turn = self.plans[r.user].turns[r.index]
        heapq.heappush(self.waiting, (t + turn.think_s, r.user))

    def _after_step(self, finished, s: int) -> None:
        """Which rows decoded in step ``s`` and at what context, and the
        step of each request's first token (for the per-layer counts)."""
        ctx = []
        for rid, (m, reused, emitted, _) in rows_mod.active(
                self.sched).items():
            ctx.append(m + emitted - 1)
            self.admitted.setdefault(rid, (s, m, reused))
        for h in finished:
            res = h.result
            if res is None:
                continue
            self.admitted.setdefault(
                h.request_id, (s, res.prompt_tokens, res.reuse_depth))
            if res.gen_tokens >= 2:
                ctx.append(res.prompt_tokens + res.gen_tokens - 1)
        self.decode_ctx.append(ctx)

    def run_until(self, until: float) -> None:
        """Serve until the first step boundary at or after ``until``."""
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            self._submit_due(now)
            if not (self.sched.in_flight or self.sched.pending()):
                nxt = self.waiting[0][0] if self.waiting else until
                time.sleep(max(0.0, min(nxt, until) - now))
                continue
            with self.annotate("bench:step"):
                finished = self.sched.step()
            t = time.perf_counter()
            self.steps.append((now, t))
            for h in finished:
                self._complete(h, t)
            self._after_step(finished, len(self.steps) - 1)


def warm_up(sched, seed: int, shapes: List[int]) -> List[Req]:
    """Serve one cold request per prompt length in ``shapes`` to the end,
    so that every chunk width, the decode step, the row set-up and
    release and the table update compile (or load) before the pre-roll.
    Returns them as requests for the cold part of the comparison."""
    handles = [sched.submit(
        traffic.user_text(seed, (1 << 30) + 1024 + i, 0, n - 1),
        max_new_tokens=WARMUP_NEW, admit=False)
        for i, n in enumerate(shapes)]
    while sched.in_flight or sched.pending():
        sched.step()
    out = []
    for i, h in enumerate(handles):
        out.append(Req(h.request_id, -1, i, 0.0, 0.0))
        _record(out[-1], h)
    return out


def warmup_shapes(chunk_shapes) -> List[int]:
    """Cold prompt lengths that make the engine run each chunk width once:
    one full widest chunk, then a remainder of exactly the next width.
    Each ends on a page boundary, so the decode step after it allocates
    a page and runs the table update."""
    widest = max(chunk_shapes)
    return [widest + c for c in sorted(chunk_shapes) if c < widest] or [
        widest]


def tokens_emitted(loop: Loop, t: float, snap: dict) -> int:
    """Tokens emitted by moment ``t`` (a step boundary): every token of
    the requests completed by then, plus the in-flight rows' counts."""
    done = sum(r.gen for r in loop.reqs.values()
               if r.done is not None and r.done <= t)
    return done + sum(e for (_, _, e, _) in snap.values())
