"""The chunked prefill's share of the chip's bf16 peak (model step
layer): the model FLOPs of the fresh prompt tokens of the admissions
whose first token came in the traced window, over the device time of the
chunked-prefill programs (``jit__chunk_prefill``) times the peak."""
from harness import flops

PROGRAM = "jit__chunk_prefill"


def read(w):
    if w.trace is None or not w.peaks:
        return None
    t = w.trace["module_s"].get(PROGRAM, 0.0)
    if t <= 0:
        return None
    work = sum(flops.prefill_flops(w.dims, reused, m)
               for m, reused in w.admissions)
    return 100.0 * work / (t * w.peaks["bf16_flops"])
