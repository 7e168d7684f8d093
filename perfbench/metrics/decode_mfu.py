"""The decode step's share of the chip's bf16 peak (model step layer):
the model FLOPs of every row decoded in the traced window (weights,
attention at each row's valid context, one logit row), over the device
time of the compiled decode step (``jit__paged_step``) times the peak."""
from harness import flops

PROGRAM = "jit__paged_step"


def read(w):
    if w.trace is None or not w.peaks:
        return None
    t = w.trace["module_s"].get(PROGRAM, 0.0)
    if t <= 0:
        return None
    work = sum(flops.decode_token_flops(w.dims, c)
               for step in w.decode_ctx for c in step)
    return 100.0 * work / (t * w.peaks["bf16_flops"])
