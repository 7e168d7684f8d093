"""Random weights from the seed, made on the device in one jitted call,
in the program's parameter layout and the types they are served in."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.traffic import seed_ints

# Matrices are normal with std 1/sqrt(fan-in), embeddings with std 0.02.
# GPT-2's own initialisation (0.02 everywhere, residual projections
# smaller still) leaves the residual stream of an untrained model
# dominated by the input token's embedding: through the tied unembedding
# it then repeats its last input token whatever the context, and neither
# the cache nor attention could change a served token.  With fan-in
# scaled matrices every layer moves the stream and the logits are nearly
# flat, so a wrong cache entry shows.  Biases and LayerNorm parameters
# are drawn too, so that the comparison with the reference covers them.
STD = 0.02


def make_params(model: dict, seed: int):
    """The program's dense-decoder tree: ``embed`` {wte, wpe}, ``seg0``
    (layers stacked on axis 0) and ``final_norm``."""
    # 31 bits: a key from any seed, within int32 without 64-bit mode
    key = jax.random.PRNGKey(int(seed_ints(seed, 1)[0]) >> 1)
    return jax.block_until_ready(jax.jit(initializer(model))(key))


def initializer(model: dict):
    """The function of a PRNG key that makes the weights."""
    L, d = model["num_layers"], model["d_model"]
    H, Hkv = model["num_heads"], model["num_kv_heads"]
    dh = model.get("head_dim") or d // H
    ff, V, P = model["d_ff"], model["vocab_size"], model["max_seq_len"]
    wdt = jnp.dtype(model["param_dtype"])

    def init(key):
        ks = iter(jax.random.split(key, 32))

        def n(shape, std, dtype=wdt):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * std).astype(dtype)

        def w(*shape):                      # (layers, fan_in, fan_out)
            return n(shape, 1.0 / math.sqrt(shape[-2]))

        def norm(*lead):
            return {"w": 1.0 + n(lead + (d,), STD, jnp.float32),
                    "b": n(lead + (d,), STD, jnp.float32)}

        return {
            "embed": {"wte": n((V, d), STD), "wpe": n((P, d), STD)},
            "seg0": {
                "ln1": norm(L),
                "attn": {"wq": w(L, d, H * dh),
                         "wk": w(L, d, Hkv * dh),
                         "wv": w(L, d, Hkv * dh),
                         "wo": w(L, H * dh, d),
                         "bq": n((L, H * dh), STD),
                         "bk": n((L, Hkv * dh), STD),
                         "bv": n((L, Hkv * dh), STD)},
                "ln2": norm(L),
                "ffn": {"w_up": w(L, d, ff),
                        "b_up": n((L, ff), STD),
                        "w_down": w(L, ff, d),
                        "b_down": n((L, d), STD)},
            },
            "final_norm": norm(),
        }

    return init
