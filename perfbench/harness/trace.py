"""From the profiler's trace to numbers: busy and idle time on the device,
device time by operation and by compiled program, and idle gaps put
against what the benchmark's host was doing.

Two steps, kept apart so that the second can be checked on a small
recorded trace (``tests/fixtures/trace_small.json``):

``extract``  reads the ``.xplane.pb`` the JAX profiler wrote into plain
             lists: device operations and device programs
             ([name, start_ns, dur_ns, device]) and the benchmark's own
             host spans ([name, start_ns, dur_ns], those named ``bench:``).
``reduce``   works only on those lists.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
_SUFFIX = re.compile(r"(\.\d+|\(\d+\)|-\d+)+$")


def base_name(name: str) -> str:
    """An operation's or program's name without its HLO text and the
    numbers XLA appends (``%fusion.12 = bf16[8] fusion(...)`` ->
    ``fusion``, ``jit__paged_step(7)`` -> ``jit__paged_step``)."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def profile_options():
    import jax
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0         # no per-call Python events
    po.host_tracer_level = 1           # the benchmark's TraceAnnotations
    return po


def extract(log_dir: str) -> dict:
    """The lists ``reduce`` reads, from the newest trace under log_dir."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops: List[list] = []
    modules: List[list] = []
    spans: List[list] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                dev = int(m.group(1))
                for e in line.events:
                    dest.append([e.name, int(e.start_ns),
                                 int(e.duration_ns), dev])
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    return {"ops": ops, "modules": modules, "spans": spans}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ev, w0, w1):
    s, e = max(ev[1], w0), min(ev[1] + ev[2], w1)
    return (s, e) if e > s else None


def _self_times(spans):
    """Self time of each (start, end) span of one device: its length less
    the spans nested in it (a ``while`` holds the ops of its body)."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][0],
                                                     -spans[i][1]))
    self_t = [e - s for s, e in spans]
    stack: List[int] = []
    for i in order:
        s, e = spans[i]
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            self_t[p] -= min(e, spans[p][1]) - s
        stack.append(i)
    return [max(t, 0) for t in self_t]


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and idle time inside the benchmark's window span, averaged
    over the devices that ran anything; device seconds and call counts by
    operation and by program; the longest idle gaps by what the host was
    doing in them."""
    win = [s for s in tr["spans"] if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench:window span")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    window_s = (w1 - w0) / 1e9
    by_dev = defaultdict(list)
    names = defaultdict(list)
    for ev in tr["ops"]:
        c = _clip(ev, w0, w1)
        if c is not None:
            by_dev[ev[3]].append(c)
            names[ev[3]].append(base_name(ev[0]))
    # device seconds by operation are self times, so that a loop and the
    # operations of its body are not counted twice
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    for dev, spans in by_dev.items():
        for name, t in zip(names[dev], _self_times(spans)):
            op_s[name] += t / 1e9
            op_n[name] += 1
    mod_s: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    mods = []
    for ev in tr["modules"]:
        c = _clip(ev, w0, w1)
        if c is None:
            continue
        name = base_name(ev[0])
        mod_s[name] += (c[1] - c[0]) / 1e9
        mod_n[name] += 1
        mods.append((c[0], c[1], name))
    mods.sort()
    busy = {d: _union(iv) for d, iv in by_dev.items()}
    busy_s = (sum(sum(e - s for s, e in u) for u in busy.values())
              / max(len(busy), 1) / 1e9)
    # idle gaps of the first device, named by the host span that holds
    # the gap's start and the program that ran last before it
    host = sorted((s[1], s[1] + s[2], s[0]) for s in tr["spans"]
                  if s[0] != WINDOW_SPAN)
    host_starts = [h[0] for h in host]
    mod_starts = [m[0] for m in mods]
    gaps: Dict[str, float] = defaultdict(float)
    if busy:
        u = busy[min(busy)]
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            i = bisect.bisect_right(host_starts, g0) - 1
            span = host[i][2] if i >= 0 and g0 < host[i][1] else "no_span"
            j = bisect.bisect_left(mod_starts, g0) - 1
            last = mods[j][2] if j >= 0 else "window_start"
            gaps[f"{span} after {last}"] += (g1 - g0) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(busy),
        "op_s": dict(op_s), "op_n": dict(op_n),
        "module_s": dict(mod_s), "module_n": dict(mod_n),
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }
