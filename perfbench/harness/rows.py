"""The in-flight rows as the engine holds them.

The scheduler hands a request back only when it completes, so what the
rows hold between steps (tokens emitted so far, when the first came) is
read from ``PagedEngine``'s per-row records.  This is the one place the
benchmark reads engine internals; everything else goes through the
scheduler's public surface and ``GenResult``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

Row = Tuple[int, int, int, Optional[float]]   # m, reused, emitted, first


def snapshot(sched) -> Dict[int, Row]:
    """request id -> (prompt tokens, reused tokens, tokens emitted, first
    token stamp or None) for every in-flight request; a row still in
    chunked admission has emitted nothing."""
    eng = sched.engine
    out: Dict[int, Row] = {}
    for slot, req in sched.in_flight.items():
        st = eng._slots[slot]
        if st is None:                        # chunked admission pending
            out[req.request_id] = (0, 0, 0, None)
            continue
        emitted = len(st.resume_emitted) + len(st.emitted)
        out[req.request_id] = (st.m, st.depth, emitted,
                               st.t_first if emitted else None)
    return out


def active(sched) -> Dict[int, Row]:
    """The rows of ``snapshot`` that decode: their admission is done."""
    return {k: v for k, v in snapshot(sched).items() if v[2] > 0}
