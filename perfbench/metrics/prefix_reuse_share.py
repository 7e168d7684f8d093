"""Share of admitted prompt tokens served from the cache in the window
(admission and recycling tier): reused / (reused + prefilled), from the
engine's ``tokens_reused`` and ``tokens_prefilled`` counters."""


def read(w):
    reused = (w.counters1["engine.tokens_reused"]
              - w.counters0["engine.tokens_reused"])
    fresh = (w.counters1["engine.tokens_prefilled"]
             - w.counters0["engine.tokens_prefilled"])
    if reused + fresh <= 0:
        return None
    return 100.0 * reused / (reused + fresh)
