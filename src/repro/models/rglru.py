"""RG-LRU recurrent block (Griffin / RecurrentGemma, arXiv:2402.19427).

Temporal mixing:  h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
with a_t = exp(-c * softplus(Lambda) * r_t),  r/i gates sigmoid-linear in the
(causal-conv'd) input branch.  Prefill uses ``lax.associative_scan`` in f32;
decode is a single fused step.  The recycled "cache" for this family is the
(h, conv tail) state snapshot (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import dense_init, split_tree

_C = 8.0  # Griffin's fixed scalar on softplus(Lambda)


def init_rglru(cfg: ModelConfig, key, dtype):
    hc = cfg.hybrid
    d, w = cfg.d_model, (hc.lru_width or cfg.d_model)
    ks = split_tree(key, 7)
    return {
        "w_x": dense_init(ks[0], (d, w), dtype),
        "w_gate": dense_init(ks[1], (d, w), dtype),
        "conv_k": dense_init(ks[2], (hc.conv1d_width, w), dtype, scale=0.1),
        "conv_b": jnp.zeros((w,), dtype),
        "w_a": dense_init(ks[3], (w, w), dtype),
        "b_a": jnp.zeros((w,), jnp.float32),
        "w_i": dense_init(ks[4], (w, w), dtype),
        "b_i": jnp.zeros((w,), jnp.float32),
        # Lambda init so a^c in (0.9, 0.999) — Griffin appendix
        "lam": jax.random.uniform(ks[5], (w,), jnp.float32, 2.0, 5.0),
        "w_out": dense_init(ks[6], (w, d), dtype),
    }


def init_rglru_state(cfg: ModelConfig, batch: int, dtype):
    hc = cfg.hybrid
    w = hc.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, hc.conv1d_width - 1, w), dtype),
    }


def _causal_conv(p, x, conv_state):
    """x: (B,S,w); conv_state: (B, cw-1, w) tail of previous tokens."""
    cw = p["conv_k"].shape[0]
    xp = jnp.concatenate([conv_state, x], axis=1)            # (B, S+cw-1, w)
    out = sum(xp[:, i:i + x.shape[1]] * p["conv_k"][i] for i in range(cw))
    new_state = xp[:, -(cw - 1):] if cw > 1 else conv_state
    return out + p["conv_b"], new_state


def _gates(p, xb):
    """a_t (log-space f32) and gated input from conv'd branch xb (B,S,w)."""
    xf = xb.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["w_a"].astype(jnp.float32) + p["b_a"])
    i = jax.nn.sigmoid(xf @ p["w_i"].astype(jnp.float32) + p["b_i"])
    log_a = -_C * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) in log space for stability
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, mult * (i * xf)


def rglru_prefill(cfg: ModelConfig, p, x, state, rt=None):
    """x: (B,S,d) -> (y, new_state).  Associative scan over time."""
    B, S, _ = x.shape
    xb = x @ p["w_x"]
    xb, conv_state = _causal_conv(p, xb, state["conv"])
    a, bx = _gates(p, xb)                                    # (B,S,w) f32

    if rt is not None and rt.use_pallas and x.shape[1] > 1:
        from repro.kernels import ops
        h, _ = ops.rglru_scan(a, bx, state["h"])
    else:
        # fold in carried state: h_t = (prod a_1..t) h_0 + scan(b)
        def binop(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, b1 * a2 + b2

        a_sc, b_sc = jax.lax.associative_scan(binop, (a, bx), axis=1)
        h = b_sc + a_sc * state["h"][:, None, :]
    gate = jax.nn.gelu((x @ p["w_gate"]).astype(jnp.float32))
    y = (h * gate).astype(x.dtype) @ p["w_out"]
    new_state = {"h": h[:, -1], "conv": conv_state}
    return y, new_state


def rglru_decode(cfg: ModelConfig, p, x, state, rt=None):
    """x: (B,1,d) single step."""
    xb = x @ p["w_x"]
    xb, conv_state = _causal_conv(p, xb, state["conv"])
    a, bx = _gates(p, xb)
    h = a[:, 0] * state["h"] + bx[:, 0]                      # (B,w)
    gate = jax.nn.gelu((x @ p["w_gate"]).astype(jnp.float32))
    y = (h[:, None] * gate).astype(x.dtype) @ p["w_out"]
    return y, {"h": h, "conv": conv_state}
