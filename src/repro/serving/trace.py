"""Host spans of the serving path, on the profiler's own clock.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: the same
mechanism and clock as the device planes of a profiler trace, so each idle
gap on the device can be put down to what the host was doing in it.  A
span is recorded only while a trace runs (``jax.profiler.start_trace`` or
``start_server``) and costs about a microsecond when none does.  Keyword
arguments travel as the event's stats, not in its name.

``install_gc_spans()`` adds one ``gc`` span around every collection of
generation 1 or 2 (generation 0 is too short and too frequent to matter).
"""
from __future__ import annotations

import gc

import jax

span = jax.profiler.TraceAnnotation

_open_gc: list = []          # the span of the collection under way, if any


def _gc_span(phase: str, info: dict) -> None:
    if info["generation"] < 1:
        return
    if phase == "start":
        s = span("gc", generation=info["generation"])
        s.__enter__()
        _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Register the ``gc`` span hook once per process (``gc.callbacks`` is
    the interpreter's own, so the hook is too); later calls do nothing."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
