"""Plain reference of the GPT-2 decoder block (DialoGPT-medium and -large).

Written from the published description (Radford et al. 2019; the Hugging
Face ``GPT2Model``): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention with biased Q/K/V projections and a
GELU (tanh) MLP, a final LayerNorm and logits through the tied token
embedding.  It imports nothing of the program under test.  Everything
runs in float32 at ``Precision.HIGHEST``, layer by layer under one scan.

Departure from the published block: the attention output projection's
bias (GPT-2's ``attn.c_proj.bias``) is absent, because the program's
block has no such parameter; the benchmark's weights carry none, which is
the published block with that bias at zero.

``quant="fp8"`` computes every weight matmul (and the logits) with both
operands rounded to float8 e4m3 under per-row / per-output-channel absmax
scales: the control, one precision step below the bf16 the configuration
states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (S, a) @ w (a, b) in float32, or in fp8 for the control."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + LN_EPS) * p["w"].astype(jnp.float32)
            + p["b"].astype(jnp.float32))


def _block(x, p, *, heads, quant):
    S, d = x.shape
    dh = d // heads
    a = p["attn"]
    h = _ln(x, p["ln1"])
    q = (_mm(h, a["wq"], quant) + a["bq"].astype(jnp.float32))
    k = (_mm(h, a["wk"], quant) + a["bk"].astype(jnp.float32))
    v = (_mm(h, a["wv"], quant) + a["bv"].astype(jnp.float32))
    q, k, v = (t.reshape(S, heads, dh) for t in (q, k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(S, d)
    x = x + _mm(o, a["wo"], quant)
    f = p["ffn"]
    h = _ln(x, p["ln2"])
    h = jax.nn.gelu(_mm(h, f["w_up"], quant) + f["b_up"].astype(jnp.float32),
                    approximate=True)
    return x + _mm(h, f["w_down"], quant) + f["b_down"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def logits_at(params, ids, positions, *, heads, quant=None):
    """Logits (len(positions), V) after tokens ids[: p + 1] for each p.

    ``ids`` (S,) int32 may be padded at the end: causal attention keeps
    padding from reaching any earlier position."""
    emb = params["embed"]
    S = ids.shape[0]
    x = (emb["wte"][ids].astype(jnp.float32)
         + emb["wpe"][:S].astype(jnp.float32))

    def body(x, p):
        return _block(x, p, heads=heads, quant=quant), None

    x, _ = jax.lax.scan(body, x, params["seg0"])
    h = _ln(x[positions], params["final_norm"])
    return _mm(h, emb["wte"].T, quant)


def served_logits(params, model: dict, ids, m: int, *, quant=None,
                  width: int = 128):
    """Logits at each served position of a request whose first ``m`` of
    ``ids`` are its prompt: row j scores the token ids[m + j]."""
    ids = np.asarray(ids, np.int32)
    n = len(ids) - m
    if n > width:
        raise ValueError(f"{n} served tokens exceed the width {width}")
    S = model["max_seq_len"]
    pad = np.zeros(S, np.int32)
    pad[:len(ids)] = ids
    pos = np.full(width, m - 1, np.int32)
    pos[:n] = np.arange(m - 1, m - 1 + n)
    out = logits_at(params, jnp.asarray(pad), jnp.asarray(pos),
                    heads=model["num_heads"], quant=quant)
    return np.asarray(out[:n])
