"""Mean device time of one chunked-prefill program call in the traced
window (model step layer), in milliseconds."""

PROGRAM = "jit__chunk_prefill"


def read(w):
    if w.trace is None:
        return None
    n = w.trace["module_n"].get(PROGRAM, 0)
    if n <= 0:
        return None
    return 1e3 * w.trace["module_s"][PROGRAM] / n
