"""``paged_decode_attention``'s share of its roofline (kernels layer):
the least time of the decode attention the traced window needed, over
the kernel's summed device time.  The least time of each step is its
rows' K/V bytes at their valid contexts over the peak bandwidth: at about
1 FLOP per byte the bound is bytes."""
from harness import flops

KERNEL = "paged_decode_attention"


def read(w):
    if w.trace is None or not w.peaks:
        return None
    t = w.trace["op_s"].get(KERNEL, 0.0)
    if t <= 0:
        return None
    least = 0.0
    for step in w.decode_ctx:
        costs = [flops.decode_attn_cost(w.dims, c) for c in step]
        if costs:
            least += flops.least_time(sum(c[0] for c in costs),
                                      sum(c[1] for c in costs), w.peaks)
    return 100.0 * least / t
