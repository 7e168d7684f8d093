"""RWKV-6 "Finch" time/channel mixing (arXiv:2404.05892).

Attention-free: per-head matrix-valued state S (D x D) with data-dependent
per-channel decay  S_t = diag(w_t) S_{t-1} + k_t^T v_t  and readout
y_t = r_t (S_{t-1} + diag(u) k_t^T v_t).  Decode state is O(1) in sequence
length — the recycled "cache" is the (S, shift) snapshot, and long_500k is
native.  Prefill is a time scan in jnp; the chunked Pallas kernel
(``repro.kernels.rwkv6_wkv``) computes the same recurrence blockwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import dense_init, split_tree, rmsnorm

_LORA_DIM = 64


def init_rwkv_tmix(cfg: ModelConfig, key, dtype):
    d = cfg.d_model
    D = cfg.rwkv.head_dim
    H = d // D
    ks = split_tree(key, 10)
    return {
        "mu_w": jnp.full((d,), 0.5, jnp.float32),
        "mu_k": jnp.full((d,), 0.5, jnp.float32),
        "mu_v": jnp.full((d,), 0.5, jnp.float32),
        "mu_r": jnp.full((d,), 0.5, jnp.float32),
        "mu_g": jnp.full((d,), 0.5, jnp.float32),
        "w0": jnp.full((d,), -1.0, jnp.float32),     # decay bias (pre -exp(exp))
        "lora_a": dense_init(ks[0], (d, _LORA_DIM), dtype, scale=0.01),
        "lora_b": dense_init(ks[1], (_LORA_DIM, d), dtype, scale=0.01),
        "w_r": dense_init(ks[2], (d, d), dtype),
        "w_k": dense_init(ks[3], (d, d), dtype),
        "w_v": dense_init(ks[4], (d, d), dtype),
        "w_g": dense_init(ks[5], (d, d), dtype),
        "u": dense_init(ks[6], (H, D), jnp.float32, scale=0.5),
        "ln_w": jnp.ones((d,), jnp.float32),         # per-head group norm
        "w_o": dense_init(ks[7], (d, d), dtype),
    }


def init_rwkv_cmix(cfg: ModelConfig, key, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = split_tree(key, 3)
    return {
        "mu_k": jnp.full((d,), 0.5, jnp.float32),
        "mu_r": jnp.full((d,), 0.5, jnp.float32),
        "w_k": dense_init(ks[0], (d, f), dtype),
        "w_v": dense_init(ks[1], (f, d), dtype),
        "w_r": dense_init(ks[2], (d, d), dtype),
    }


def init_rwkv_state(cfg: ModelConfig, batch: int, dtype):
    d = cfg.d_model
    D = cfg.rwkv.head_dim
    H = d // D
    return {
        "wkv": jnp.zeros((batch, H, D, D), jnp.float32),
        "shift_t": jnp.zeros((batch, d), dtype),
        "shift_c": jnp.zeros((batch, d), dtype),
    }


def _shift(x, prev):
    """token shift: x_{t-1} with carried state.  x (B,S,d), prev (B,d)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _mix(x, xp, mu):
    return x + (xp - x) * mu.astype(x.dtype)


def _decay(p, xw):
    """data-dependent per-channel decay w_t in (0,1).  xw (B,S,d)."""
    lora = jnp.tanh(xw @ p["lora_a"]) @ p["lora_b"]
    w = p["w0"] + lora.astype(jnp.float32)
    return jnp.exp(-jnp.exp(w))                              # (B,S,d)


def _wkv_step(S, w, k, v, r, u):
    """One recurrence step.  S (B,H,D,D); w,k,v,r (B,H,D); u (H,D).
    y[b,h,j] = sum_i r[i] * (S[i,j] + u[i] k[i] v[j]);
    S' = diag(w) S + k^T v."""
    kv = k[..., :, None] * v[..., None, :]                   # (B,H,D,D)
    y = jnp.einsum("bhi,bhij->bhj", r, S + u[None, :, :, None] * kv)
    S_new = w[..., :, None] * S + kv
    return S_new, y


def rwkv_tmix(cfg: ModelConfig, p, x, state, rt=None):
    """Time mixing over S steps.  Returns (y, new_state)."""
    B, S, d = x.shape
    D = cfg.rwkv.head_dim
    H = d // D
    xp = _shift(x, state["shift_t"])
    xw, xk, xv, xr, xg = (_mix(x, xp, p[f"mu_{n}"]) for n in "wkvrg")
    w = _decay(p, xw).reshape(B, S, H, D)
    k = (xk @ p["w_k"]).astype(jnp.float32).reshape(B, S, H, D)
    v = (xv @ p["w_v"]).astype(jnp.float32).reshape(B, S, H, D)
    r = (xr @ p["w_r"]).astype(jnp.float32).reshape(B, S, H, D)
    g = jax.nn.silu(xg @ p["w_g"])

    use_kernel = rt is not None and rt.use_pallas and S > 1
    if use_kernel:
        from repro.kernels import ops
        y, S_fin = ops.rwkv6_wkv(r, k, v, w, p["u"], state["wkv"])
    else:
        def step(Sm, inp):
            w_t, k_t, v_t, r_t = inp
            return _wkv_step(Sm, w_t, k_t, v_t, r_t, p["u"])

        S_fin, y = jax.lax.scan(
            step, state["wkv"],
            (w.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
             v.transpose(1, 0, 2, 3), r.transpose(1, 0, 2, 3)))
        y = y.transpose(1, 0, 2, 3)                          # (B,S,H,D)

    y = rmsnorm(y.reshape(B, S, H, D), p["ln_w"].reshape(H, D)[None, None])
    y = y.reshape(B, S, d).astype(x.dtype) * g
    new_state = {"wkv": S_fin, "shift_t": x[:, -1, :],
                 "shift_c": state["shift_c"]}
    return y @ p["w_o"], new_state


def rwkv_cmix(cfg: ModelConfig, p, x, state, rt=None):
    """Channel mixing (relu^2 MLP with token shift)."""
    xp = _shift(x, state["shift_c"])
    xk = _mix(x, xp, p["mu_k"])
    xr = _mix(x, xp, p["mu_r"])
    k = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    if rt is not None and rt.model_axes:
        k = rt.hint_last(k, rt.model_axes)
    y = jax.nn.sigmoid(xr @ p["w_r"]) * (k @ p["w_v"])
    new_state = dict(state, shift_c=x[:, -1, :])
    return y, new_state
