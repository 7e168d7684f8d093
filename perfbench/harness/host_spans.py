"""Device idle time put down to the serving program's own host spans.

The program marks its host phases as profiler spans (``sched.*``,
``engine.*`` and ``gc``; ``repro.serving.trace``), on the clock of the
device planes.  ``extract`` reads them from a trace directory as
[name, start_ns, dur_ns], the name cut at any ``#``.  ``idle_by_span``
takes the lists ``trace.extract`` returns plus those spans and splits
each idle interval of the first device inside the ``bench:window`` span
(the intervals ``trace.reduce`` names in ``idle_gaps``) over the
innermost covering host span, by overlap: a gap starts while the host is
still inside the read that the device's finish releases, so the span
that holds a gap's start is not what the host did through it.

The harness's run (``cell.py``) does not call this module yet: a
per-layer metric built on it (the share of the window idle under the
admission spans, or under the rest of the step) needs ``cell.py`` to
extract these spans before it removes the trace directory and to hand
the result to the readers.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List

from harness import trace

PROGRAM_PREFIXES = ("sched.", "engine.")
GC_SPAN = "gc"
ADMISSION_SPANS = ("engine.admission", "engine.packed_admission")
NO_SPAN = "no_span"


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES) or name == GC_SPAN


def extract(log_dir: str) -> List[list]:
    """The program's host spans in the newest trace under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out: List[list] = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if is_program_span(name):
                    out.append([name, int(e.start_ns), int(e.duration_ns)])
    return out


def _segments(host):
    """Time cut at every start and end of the (start, end, name) host
    spans: [(t0, t1, innermost, names)] over consecutive cut points, where
    ``innermost`` is the covering span that started last (on a tie the one
    that ends first) and ``names`` every covering span's name."""
    host = [h for h in host if h[1] > h[0]]
    cuts = sorted({t for s, e, _ in host for t in (s, e)})
    starts = defaultdict(list)
    for h in host:
        starts[h[0]].append(h)
    active: list = []
    segs = []
    for t0, t1 in zip(cuts, cuts[1:]):
        active = [h for h in active if h[1] > t0] + starts[t0]
        if active:
            inner = max(active, key=lambda h: (h[0], -h[1]))[2]
            segs.append((t0, t1, inner, {h[2] for h in active}))
        else:
            segs.append((t0, t1, NO_SPAN, set()))
    return segs


def idle_by_span(tr: dict, program: List[list]) -> dict:
    """Idle seconds of the first device in the window by innermost host
    span (``idle_by_span``, ``no_span`` where none covers), by every span
    name that holds them (``idle_under_span``), and the number of the
    program's spans inside the window (``program_span_count``).  The
    host spans are the program's and the benchmark's ``bench:`` spans
    other than the window."""
    win = [s for s in tr["spans"] if s[0] == trace.WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench:window span")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    busy = defaultdict(list)
    for ev in tr["ops"]:
        c = trace._clip(ev, w0, w1)
        if c is not None:
            busy[ev[3]].append(c)
    gaps = []
    if busy:
        u = trace._union(busy[min(busy)])
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
    inside = [(p[1], p[1] + p[2], p[0]) for p in program
              if p[1] < w1 and p[1] + p[2] > w0]
    host = inside + [(s[1], s[1] + s[2], s[0]) for s in tr["spans"]
                     if s[0] != trace.WINDOW_SPAN]
    segs = _segments(host)
    seg_starts = [g[0] for g in segs]
    inner: Dict[str, float] = defaultdict(float)
    under: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        covered = 0
        i = max(bisect.bisect_right(seg_starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            t0, t1, name, names = segs[i]
            ov = min(t1, g1) - max(t0, g0)
            if ov > 0:
                covered += ov
                inner[name] += ov / 1e9
                for n in names:
                    under[n] += ov / 1e9
            i += 1
        if g1 - g0 > covered:
            inner[NO_SPAN] += (g1 - g0 - covered) / 1e9
    return {"idle_by_span": dict(inner), "idle_under_span": dict(under),
            "program_span_count": len(inside)}


def host_idle_s(r: dict):
    """From ``idle_by_span``'s result: (idle seconds under an admission
    span at any depth, idle seconds under the program's other spans),
    or None when the window holds no program span."""
    if not r["program_span_count"]:
        return None
    admission = sum(r["idle_under_span"].get(n, 0.0)
                    for n in ADMISSION_SPANS)
    program = sum(v for n, v in r["idle_by_span"].items()
                  if is_program_span(n))
    return admission, program - admission
