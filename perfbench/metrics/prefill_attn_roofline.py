"""``paged_prefill_attention``'s share of its roofline (kernels layer):
the least time of the causal attention of the fresh prompt tokens of the
admissions whose first token came in the traced window, over the
kernel's summed device time.  Fresh spans of tens of tokens make about
tens of FLOPs per byte, below the chip's ridge, so the bound is bytes."""
from harness import flops

KERNEL = "paged_prefill_attention"


def read(w):
    if w.trace is None or not w.peaks:
        return None
    t = w.trace["op_s"].get(KERNEL, 0.0)
    if t <= 0 or not w.admissions:
        return None
    least = sum(flops.least_time(*flops.prefill_attn_cost(w.dims, r, m),
                                 w.peaks)
                for m, r in w.admissions if m > r)
    return 100.0 * least / t
