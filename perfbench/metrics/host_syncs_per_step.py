"""Blocking device-to-host reads per decode step over the window
(scheduler layer): the window's change in the engine's
``stats["host_syncs"]`` over its change in the scheduler's
``stats["decode_steps"]``."""


def read(w):
    if "engine.host_syncs" not in w.counters1:
        return None
    steps = w.counters1["decode_steps"] - w.counters0["decode_steps"]
    if steps <= 0:
        return None
    return (w.counters1["engine.host_syncs"]
            - w.counters0["engine.host_syncs"]) / steps
