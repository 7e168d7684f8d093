"""Peaks of the chips, and the operations and bytes that the algorithm
needs, computed from shapes.

Every count here is what the mathematics asks for at the rows' valid
context lengths: never the padded table width, the padded chunk width, or
the blocks that today's kernels happen to read.  A kernel that skips
padding, or a fused step that replaces these kernels, then reads higher
against the same work.
"""
from __future__ import annotations

from dataclasses import dataclass

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


@dataclass(frozen=True)
class Dims:
    """The shapes the counts need (a dense decoder with MHA/GQA)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2          # bytes per stored K or V element (bf16)

    @classmethod
    def from_model(cls, m: dict) -> "Dims":
        return cls(layers=m["num_layers"], d_model=m["d_model"],
                   heads=m["num_heads"], kv_heads=m["num_kv_heads"],
                   head_dim=(m.get("head_dim")
                             or m["d_model"] // m["num_heads"]),
                   d_ff=m["d_ff"], vocab=m["vocab_size"])


def matmul_flops_per_token(d: Dims) -> int:
    """Weight matmuls of one token through the trunk (Q, K, V, output
    projection and the two MLP matrices of every layer), 2 per MAC.  The
    unembedding is counted apart, once per logit row that is needed."""
    qkvo = d.d_model * (d.heads + 2 * d.kv_heads) * d.head_dim \
        + d.heads * d.head_dim * d.d_model
    mlp = 2 * d.d_model * d.d_ff
    return 2 * d.layers * (qkvo + mlp)


def unembed_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def attn_flops(d: Dims, ctx: int) -> int:
    """One query against ``ctx`` keys in every layer: QK^T and PV."""
    return 4 * d.layers * d.heads * d.head_dim * ctx


def decode_token_flops(d: Dims, ctx: int) -> int:
    """Model FLOPs of one decoded token that attends ``ctx`` positions."""
    return matmul_flops_per_token(d) + attn_flops(d, ctx) + unembed_flops(d)


def decode_attn_cost(d: Dims, ctx: int) -> tuple:
    """(flops, bytes) of one row's decode attention over ``ctx`` positions
    in every layer: K and V of the context read once, the query read and
    the output written once.  Intensity is about 1 FLOP/byte, so the
    bound is bytes."""
    flops = attn_flops(d, ctx)
    kv = 2 * ctx * d.kv_heads * d.head_dim * d.kv_bytes
    qo = 2 * d.heads * d.head_dim * d.kv_bytes
    return flops, d.layers * (kv + qo)


def prefill_attn_cost(d: Dims, reused: int, total: int) -> tuple:
    """(flops, bytes) of the causal attention of fresh positions
    [reused, total) over every earlier position, in every layer: the
    context's K and V read once, the fresh K and V written once, and the
    fresh queries read and outputs written once."""
    fresh = total - reused
    # sum over p in [reused, total) of (p + 1) keys
    keys = (total * (total + 1) - reused * (reused + 1)) // 2
    flops = 4 * d.layers * d.heads * d.head_dim * keys
    kv_read = 2 * total * d.kv_heads * d.head_dim * d.kv_bytes
    kv_write = 2 * fresh * d.kv_heads * d.head_dim * d.kv_bytes
    qo = 2 * fresh * d.heads * d.head_dim * d.kv_bytes
    return flops, d.layers * (kv_read + kv_write + qo)


def prefill_flops(d: Dims, reused: int, total: int) -> int:
    """Model FLOPs of admitting a prompt of ``total`` tokens whose first
    ``reused`` are served from the cache: the fresh tokens through the
    trunk, their attention, and one logit row for the first token."""
    fresh = total - reused
    if fresh <= 0:
        return 0
    keys = (total * (total + 1) - reused * (reused + 1)) // 2
    return (fresh * matmul_flops_per_token(d)
            + 4 * d.layers * d.heads * d.head_dim * keys
            + unembed_flops(d))


def least_time(flops: float, nbytes: float, pk: dict) -> float:
    """Roofline: the larger of compute time at peak and bytes at peak."""
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
