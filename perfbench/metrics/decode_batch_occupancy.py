"""Rows held per decode step over the window (scheduler layer): the
window's change in ``stats["occupancy_sum"]`` over its change in
``stats["decode_steps"]``.  Rows still in chunked admission count, as the
scheduler counts them."""


def read(w):
    steps = w.counters1["decode_steps"] - w.counters0["decode_steps"]
    if steps <= 0:
        return None
    return (w.counters1["occupancy_sum"]
            - w.counters0["occupancy_sum"]) / steps
