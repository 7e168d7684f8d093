"""Attention: GQA/MHA with RoPE, qk-norm, bias options, sliding window, and a
KV cache designed for cross-prompt recycling.

Three execution paths:
  * ``attend_chunked`` — memory-efficient online-softmax attention in pure
    jnp (nested lax.scan over q/kv chunks).  This is the default model path:
    it lowers cleanly for the 32k prefill shapes without materializing
    (S x S) score tensors.
  * ``attend_direct`` — small-shape direct softmax (decode steps, tests).
  * Pallas kernels (``repro.kernels``) — selected via ``Runtime.use_pallas``;
    validated in interpret mode against ``repro.kernels.ref``.

The KV cache is a slot buffer ``{"k": (B, C, Hkv, Dh), "v": ..., "slot_pos":
(C,) int32}`` where ``slot_pos[j]`` is the absolute token position held in
slot j (-1 = empty).  A full cache is the special case capacity == max_len;
a sliding-window ring cache just uses capacity == window.  Keys are stored
*post-RoPE* so recycled prefixes are position-correct by construction
(DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core.quant import dequantize_vectors_jnp, quantize_vectors_jnp
from repro.models.layers import dense_init, rmsnorm, split_tree, apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(cfg: ModelConfig, key, dtype, *, cross: bool = False):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = split_tree(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, h * dh), dtype),
        "wk": dense_init(ks[1], (d, hkv * dh), dtype),
        "wv": dense_init(ks[2], (d, hkv * dh), dtype),
        "wo": dense_init(ks[3], (h * dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def project_qkv(cfg: ModelConfig, p, x, positions, *, rope: bool = True):
    """x: (B, S, d) -> q (B, S, H, Dh), k/v (B, S, Hkv, Dh); RoPE applied."""
    B, S, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, h, dh)
    k = k.reshape(B, S, hkv, dh)
    v = v.reshape(B, S, hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if rope and cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------
def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int):
    """(..., Sq, Skv) additive bias from absolute positions.  kv_pos == -1
    marks an empty cache slot.  Positions may carry a leading batch axis
    (per-slot pools: q_pos (B, Sq), kv_pos (B, Skv)) — broadcasting yields a
    per-row (B, Sq, Skv) bias."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    ok = kp >= 0
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > qp - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# direct attention (small Sq — decode steps, tests, oracle)
# ---------------------------------------------------------------------------
def attend_direct(q, k, v, q_pos, kv_pos, *, causal=True, window=0, scale=None):
    """q: (B,Sq,H,Dh); k,v: (B,Skv,Hkv,Dh); positions int32 (Sq,)/(Skv,)."""
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = scale or (Dh ** -0.5)
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    # f32 accumulation via preferred_element_type — NOT operand .astype,
    # which would materialize an f32 copy of the whole KV cache (XLA hoists
    # the convert out of the layer scan; see EXPERIMENTS.md §Perf kimi).
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window)
    if bias.ndim == 3:          # per-row positions: align B with scores' B,
        bias = bias[:, None, None]  # not with the grouped-head axes
    scores = scores + bias
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# chunked online-softmax attention (prefill path)
# ---------------------------------------------------------------------------
def _div_le(n: int, target: int) -> int:
    """Largest divisor of n that is <= target."""
    t = max(min(target, n), 1)
    while n % t:
        t -= 1
    return t


def pick_chunks(B, H, Sq, Skv, *, q_chunk=512, kv_chunk=1024,
                budget_bytes=32 << 30):
    """Chunk sizes whose f32 score block (B,H,qc,kc) fits the budget —
    training shapes multiply B and H into the block, so fixed chunks OOM.
    Shapes here are GLOBAL (pre-GSPMD); the default budget assumes the block
    shards ~256-way on the production mesh (~128 MB per device)."""
    qc = _div_le(Sq, q_chunk)
    per = max(B * H * 4, 1)
    kc = _div_le(Skv, max(min(kv_chunk, budget_bytes // (per * qc)), 1))
    while B * H * qc * kc * 4 > budget_bytes and qc > 1:
        qc = _div_le(Sq, qc // 2)
        kc = _div_le(Skv, max(budget_bytes // (per * qc), 1))
    return qc, kc


def attend_chunked(q, k, v, q_pos, kv_pos, *, causal=True, window=0,
                   q_chunk=512, kv_chunk=1024, scale=None, ordered=True):
    """Flash-style two-level scan: O(Sq * kv_chunk) live memory.

    With ``window`` set, each q-chunk only visits the statically-sized kv
    range [q0 - window_pad, q0 + q_chunk) so prefill FLOPs are O(S * W),
    not O(S^2) — this is what makes recurrentgemma local-attention prefill
    sub-quadratic.
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale or (Dh ** -0.5)
    qc, kc = pick_chunks(B, H, Sq, Skv, q_chunk=q_chunk, kv_chunk=kv_chunk)
    nq, nk = Sq // qc, Skv // kc

    qg = q.reshape(B, nq, qc, Hkv, G, Dh)
    q_pos_c = q_pos.reshape(nq, qc)

    # Static per-q-chunk kv extent for windowed attention.  Only valid when
    # kv index == absolute position (``ordered``, i.e. not a wrapped ring).
    if window and causal and ordered:
        span = ((window + qc + kc - 1) // kc) * kc
        span = min(span, Skv)
    else:
        span = Skv
    nk_eff = span // kc

    @jax.checkpoint      # backward recomputes per-q-chunk (flash-bwd style);
    def q_step(_, qi):   # otherwise the inner scan saves quadratic scores
        qb = qg[:, qi]                       # (B, qc, Hkv, G, Dh)
        qp = q_pos_c[qi]                     # (qc,)
        # kv window start (static shape, dynamic offset)
        if span < Skv:
            hi = jnp.minimum((qi + 1) * qc, Skv)
            start = jnp.maximum(hi - span, 0)
        else:
            start = jnp.array(0, jnp.int32)
        kw = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        pw = jax.lax.dynamic_slice_in_dim(kv_pos, start, span, axis=0)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kw, ki * kc, kc, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vw, ki * kc, kc, axis=1)
            pb = jax.lax.dynamic_slice_in_dim(pw, ki * kc, kc, axis=0)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            s = s + _mask_bias(qp, pb, causal=causal, window=window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p_ = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p_, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p_.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk_eff, dtype=jnp.int32))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,Hkv,G,qc,Dh)
        return None, out.transpose(0, 3, 1, 2, 4)          # (B,qc,Hkv,G,Dh)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq, dtype=jnp.int32))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dh)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache ops
# ---------------------------------------------------------------------------
def init_kv_cache(batch: int, capacity: int, hkv: int, dh: int, dtype,
                  *, quant: bool = False, per_slot: bool = False):
    """Slot-buffer KV cache.  ``quant=True`` stores K/V as int8 with a
    per-(token, head) f32 scale — halves bf16 HBM reads per decode step
    (the dominant term for big MHA caches; EXPERIMENTS.md §Perf-4).

    ``per_slot=True`` gives every batch row its own ``slot_pos`` vector
    (shape (B, C) instead of (C,)) — the layout of the continuous-batching
    slot pool, where each row holds an independent request at its own
    decode position."""
    sp_shape = (batch, capacity) if per_slot else (capacity,)
    if quant:
        return {
            "k": jnp.zeros((batch, capacity, hkv, dh), jnp.int8),
            "v": jnp.zeros((batch, capacity, hkv, dh), jnp.int8),
            "k_scale": jnp.zeros((batch, capacity, hkv), jnp.float32),
            "v_scale": jnp.zeros((batch, capacity, hkv), jnp.float32),
            "slot_pos": jnp.full(sp_shape, -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, capacity, hkv, dh), dtype),
        "v": jnp.zeros((batch, capacity, hkv, dh), dtype),
        "slot_pos": jnp.full(sp_shape, -1, jnp.int32),
    }


# Symmetric per-vector int8 — ONE scheme shared with the host tier
# (repro.core.quant), so int8 K/V move host<->device without a
# dequant/requant round-trip.
_quantize_kv = quantize_vectors_jnp


def dequantize_cache(cache, dtype):
    """int8 cache view -> dense K/V (fused into the attention matmul on
    TPU; the HBM traffic is the int8 bytes)."""
    k = dequantize_vectors_jnp(cache["k"], cache["k_scale"], dtype)
    v = dequantize_vectors_jnp(cache["v"], cache["v_scale"], dtype)
    return k, v


def is_quant_cache(cache) -> bool:
    return "k_scale" in cache


def cache_write(cache, k_new, v_new, start_pos):
    """Scatter ``n`` new roped keys/values at absolute positions
    [start_pos, start_pos + n); ring-wraps when capacity < max_len."""
    C = cache["k"].shape[1]
    n = k_new.shape[1]
    pos = start_pos + jnp.arange(n, dtype=jnp.int32)
    slots = pos % C
    if is_quant_cache(cache):
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        return {
            "k": cache["k"].at[:, slots].set(kq),
            "v": cache["v"].at[:, slots].set(vq),
            "k_scale": cache["k_scale"].at[:, slots].set(ks),
            "v_scale": cache["v_scale"].at[:, slots].set(vs),
            "slot_pos": cache["slot_pos"].at[slots].set(pos),
        }
    return {
        "k": cache["k"].at[:, slots].set(k_new),
        "v": cache["v"].at[:, slots].set(v_new),
        "slot_pos": cache["slot_pos"].at[slots].set(pos),
    }


def init_paged_kv_cache(num_blocks: int, block_size: int, hkv: int, dh: int,
                        dtype, *, max_batch: int, max_blocks_per_seq: int,
                        quant: bool = False, fp_tail_blocks: int = 2):
    """Paged KV pool for one layer: ONE shared block pool plus per-request
    block tables, instead of a private dense row per request.

      k, v          (num_blocks, block_size, Hkv, Dh) — the shared pool
      block_tables  (max_batch, max_blocks_per_seq) int32 — row b's cache
                    is the pool blocks its table names, in order; entry j
                    covers absolute positions [j*bs, (j+1)*bs)

    Block 0 is the sentinel: tables are padded with it, so unused table
    entries (and inactive rows) read/write one harmless scratch block.
    Validity is *implicit* — slot j of table entry i holds position
    i*bs + j, valid iff <= the row's decode position — so no slot_pos
    array exists and blocks can be shared by any number of tables.

    ``quant=True`` stores pool K/V as int8 with a per-(token, head) f32
    scale (same scheme as the host tier, ``repro.core.quant``) — ~2-4x
    more resident blocks per HBM byte — plus a per-ROW full-precision
    **ring tail** ``k_tail/v_tail (max_batch, fp_tail_blocks*bs, Hkv,
    Dh)``: the row's most recent ``fp_tail_blocks`` blocks are attended
    in their original dtype (ring slot ``ti % fp_tail_blocks`` holds
    block ti) and only older, effectively sealed blocks go through the
    fused int8 dequant.  That is the device-tier analogue of the host
    residual tail: quantization error never sits where attention mass is
    largest."""
    cache = {
        "k": jnp.zeros((num_blocks, block_size, hkv, dh),
                       jnp.int8 if quant else dtype),
        "v": jnp.zeros((num_blocks, block_size, hkv, dh),
                       jnp.int8 if quant else dtype),
        "block_tables": jnp.zeros((max_batch, max_blocks_per_seq),
                                  jnp.int32),
    }
    if quant:
        cache["k_scale"] = jnp.zeros((num_blocks, block_size, hkv),
                                     jnp.float32)
        cache["v_scale"] = jnp.zeros((num_blocks, block_size, hkv),
                                     jnp.float32)
        cache["k_tail"] = jnp.zeros(
            (max_batch, fp_tail_blocks * block_size, hkv, dh), dtype)
        cache["v_tail"] = jnp.zeros(
            (max_batch, fp_tail_blocks * block_size, hkv, dh), dtype)
    return cache


def is_paged_cache(cache) -> bool:
    return isinstance(cache, dict) and "block_tables" in cache


def paged_cache_write(cache, k_new, v_new, pos):
    """Row b writes its one new roped K/V at absolute position ``pos[b]``
    through its block table.  The target block is exclusively owned by row
    b (copy-on-write upstream guarantees it), so rows never collide;
    inactive rows carry all-sentinel tables and scribble harmlessly on
    block 0.

    int8 pools dual-write: the quantized vector goes into the pool block
    (per-vector scales make write-time quantization identical to sealing
    the block later — each vector is quantized exactly once) and the fp
    original into the row's ring tail, so decode attention reads the most
    recent blocks at full precision."""
    bs = cache["k"].shape[1]
    B = k_new.shape[0]
    p = pos.astype(jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    blk = cache["block_tables"][rows, p // bs]
    off = p % bs
    if is_quant_cache(cache):
        kq, ks = _quantize_kv(k_new[:, 0])
        vq, vs = _quantize_kv(v_new[:, 0])
        ring = (p // bs) % (cache["k_tail"].shape[1] // bs) * bs + off
        return {
            "k": cache["k"].at[blk, off].set(kq),
            "v": cache["v"].at[blk, off].set(vq),
            "k_scale": cache["k_scale"].at[blk, off].set(ks),
            "v_scale": cache["v_scale"].at[blk, off].set(vs),
            "k_tail": cache["k_tail"].at[rows, ring].set(k_new[:, 0]),
            "v_tail": cache["v_tail"].at[rows, ring].set(v_new[:, 0]),
            "block_tables": cache["block_tables"],
        }
    return {
        "k": cache["k"].at[blk, off].set(k_new[:, 0]),
        "v": cache["v"].at[blk, off].set(v_new[:, 0]),
        "block_tables": cache["block_tables"],
    }


def paged_prefill_write(cache, k_new, v_new, row, table_row, start,
                        w_floor, n_valid):
    """Chunked-prefill scatter: write the chunk's C new roped K/V at
    absolute positions [start, start + C) of pool row ``row``, through the
    block table ``table_row`` (NBt,) — no staging cache exists.  The table
    is an explicit operand rather than ``cache["block_tables"][row]``
    because a mid-admission row's DEVICE table stays all-sentinel until
    its final chunk: the batched decode step writes through every row's
    device table (masked rows scribble block 0), so installing real block
    ids early would let a stale decode position corrupt an admission in
    progress.  ``start`` is block-aligned (the admission planner
    guarantees it); positions i >= ``n_valid`` are chunk padding and route
    to the sentinel block 0 (the designed scribble target), so a fixed
    chunk shape serves every suffix length.  Positions < ``w_floor`` are
    also dropped: a host promotion pre-uploads the entry's sub-block
    remainder [start, depth) into the boundary block, and the chunk must
    not overwrite those (exact, staged-identical) values with its own
    recomputation — its queries there exist only to pad the shape.

    int8 pools dual-write like decode: quantized codes + scales into the
    pool block (each vector's one quantization), and the fp originals into
    the row's ring tail — but only for the last R blocks the chunk
    actually writes (older in-chunk blocks would be overwritten in the
    ring anyway, and jnp scatter order for duplicate indices is
    unspecified).  Invalid ring writes are routed out of bounds and
    dropped (mode="drop") so chunk padding can never clobber a live ring
    slot of an earlier block."""
    bs = cache["k"].shape[1]
    C = k_new.shape[1]
    i = jnp.arange(C, dtype=jnp.int32)
    p = start + i
    valid = (i < n_valid) & (p >= w_floor)
    blk = jnp.where(valid, table_row[p // bs], 0)
    off = p % bs
    if is_quant_cache(cache):
        kq, ks = _quantize_kv(k_new[0])
        vq, vs = _quantize_kv(v_new[0])
        R = cache["k_tail"].shape[1] // bs
        wb = (start + n_valid - 1) // bs     # newest block this chunk seals
        ring_ok = valid & (p // bs > wb - R)
        ring = jnp.where(ring_ok, (p // bs) % R * bs + off, R * bs)
        return {
            "k": cache["k"].at[blk, off].set(kq),
            "v": cache["v"].at[blk, off].set(vq),
            "k_scale": cache["k_scale"].at[blk, off].set(ks),
            "v_scale": cache["v_scale"].at[blk, off].set(vs),
            "k_tail": cache["k_tail"].at[row, ring].set(k_new[0],
                                                        mode="drop"),
            "v_tail": cache["v_tail"].at[row, ring].set(v_new[0],
                                                        mode="drop"),
            "block_tables": cache["block_tables"],
        }
    return {
        "k": cache["k"].at[blk, off].set(k_new[0]),
        "v": cache["v"].at[blk, off].set(v_new[0]),
        "block_tables": cache["block_tables"],
    }


def paged_verify_write(cache, k_new, v_new, c0s, n_valid, act):
    """Batched multi-token speculative-verify scatter: row b writes its
    ``Cv`` new roped K/V at absolute positions [c0s[b], c0s[b] + Cv)
    through its own DEVICE table row — verification only runs on ARMED
    rows, whose tables are installed and current, and every block the
    bundle touches was speculatively reserved for (and is private to)
    the row before the round, so a rejected tail rolls back as a
    host-side table truncation.  Draft K/V written during the sparse
    draft pass are rewritten here with full-context values (the draft's
    sparse attention changes every layer's inputs, so its K/V are only
    approximations).  Positions i >= ``n_valid`` and every position of a
    row with ``act[b] == 0`` (not speculating this round) route to the
    sentinel block.

    int8 pools dual-write the ring like decode.  The engine enforces
    ``gamma <= (R-1) * block_size``, so one round's writes span at most
    R distinct blocks and every valid write's ring slot is live;
    inactive/padding ring writes are routed out of bounds and dropped."""
    bs = cache["k"].shape[1]
    B, Cv = k_new.shape[:2]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    i = jnp.arange(Cv, dtype=jnp.int32)[None]
    p = c0s.astype(jnp.int32)[:, None] + i               # (B, Cv)
    valid = (i < n_valid) & (act[:, None] > 0)
    blk = jnp.where(valid, cache["block_tables"][rows, p // bs], 0)
    off = p % bs
    if is_quant_cache(cache):
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        R = cache["k_tail"].shape[1] // bs
        ring = jnp.where(valid, (p // bs) % R * bs + off, R * bs)
        return {
            "k": cache["k"].at[blk, off].set(kq),
            "v": cache["v"].at[blk, off].set(vq),
            "k_scale": cache["k_scale"].at[blk, off].set(ks),
            "v_scale": cache["v_scale"].at[blk, off].set(vs),
            "k_tail": cache["k_tail"].at[rows, ring].set(k_new,
                                                         mode="drop"),
            "v_tail": cache["v_tail"].at[rows, ring].set(v_new,
                                                         mode="drop"),
            "block_tables": cache["block_tables"],
        }
    return {
        "k": cache["k"].at[blk, off].set(k_new),
        "v": cache["v"].at[blk, off].set(v_new),
        "block_tables": cache["block_tables"],
    }


def attend_paged_verify(q, k_chunk, v_chunk, cache, c0s):
    """Reference batched verify attention: every row's draft bundle
    (B, Cv, H, Dh) at absolute positions [c0s[b], c0s[b] + Cv) attends
    its full HISTORY (< c0) through the row's device block table and the
    bundle itself from the fresh fp operands (it seals after attention,
    like chunked prefill).  Bundle padding keys sit at positions
    >= c0 + n_valid — causally invisible to every valid query — so no
    n_valid operand exists here.

    int8 pools apply the fp-ring recency gate PER QUERY (query at qp
    reads history block t at fp iff t > qp//bs - R — exactly the window
    non-speculative decode would use at position qp) and read fp history
    from the PRE-ROUND ring snapshot riding the cache as
    ``k_tail_snap``/``v_tail_snap`` — taken anyway for the exact
    rollback restore (and equal to the live ring, since drafts never
    touch the pool); it provably covers every block any verify query
    gates to fp."""
    B, Cv, H, Dh = q.shape
    tbl = cache["block_tables"]                  # (B, NBt)
    NBt = tbl.shape[1]
    bs = cache["k"].shape[1]
    Hkv = k_chunk.shape[2]
    q_pos = c0s.astype(jnp.int32)[:, None] + jnp.arange(Cv, dtype=jnp.int32)
    hist_pos = jnp.arange(NBt * bs, dtype=jnp.int32)[None]
    hist_pos = jnp.where(hist_pos < c0s[:, None], hist_pos, -1)  # (B, Sh)
    kv_pos = jnp.concatenate([hist_pos, q_pos], axis=1)
    if not is_quant_cache(cache):
        k = cache["k"][tbl].reshape(B, NBt * bs, Hkv, Dh)
        v = cache["v"][tbl].reshape(B, NBt * bs, Hkv, Dh)
        k = jnp.concatenate([k, k_chunk.astype(k.dtype)], axis=1)
        v = jnp.concatenate([v, v_chunk.astype(v.dtype)], axis=1)
        return attend_direct(q, k, v, q_pos, kv_pos, causal=True)

    G = H // Hkv
    scale = Dh ** -0.5
    R = cache["k_tail"].shape[1] // bs
    k8 = dequantize_vectors_jnp(cache["k"][tbl], cache["k_scale"][tbl],
                                q.dtype).reshape(B, NBt * bs, Hkv, Dh)
    v8 = dequantize_vectors_jnp(cache["v"][tbl], cache["v_scale"][tbl],
                                q.dtype).reshape(B, NBt * bs, Hkv, Dh)
    ti = jnp.arange(NBt, dtype=jnp.int32)
    ring_k = (cache["k_tail_snap"].reshape(B, R, bs, Hkv, Dh)[:, ti % R]
              .reshape(B, NBt * bs, Hkv, Dh).astype(q.dtype))
    ring_v = (cache["v_tail_snap"].reshape(B, R, bs, Hkv, Dh)[:, ti % R]
              .reshape(B, NBt * bs, Hkv, Dh).astype(q.dtype))
    k_int = jnp.concatenate([k8, k_chunk.astype(q.dtype)], axis=1)
    v_int = jnp.concatenate([v8, v_chunk.astype(q.dtype)], axis=1)
    k_fp = jnp.concatenate([ring_k, k_chunk.astype(q.dtype)], axis=1)
    v_fp = jnp.concatenate([ring_v, v_chunk.astype(q.dtype)], axis=1)
    # per-(query, key) recency gate over history; bundle keys collapse to
    # the same fp operand on both views, so their gate value is moot
    gate_h = ti[None, None] > (q_pos[:, :, None] // bs) - R  # (B, Cv, NBt)
    gate_h = jnp.broadcast_to(gate_h[..., None], (B, Cv, NBt, bs))
    gate = jnp.concatenate(
        [gate_h.reshape(B, Cv, NBt * bs),
         jnp.ones((B, Cv, Cv), bool)], axis=-1)      # (B, Cv, Skv)
    qg = q.reshape(B, Cv, Hkv, G, Dh)
    s_fp = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_fp,
                      preferred_element_type=jnp.float32) * scale
    s_int = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_int,
                       preferred_element_type=jnp.float32) * scale
    gate_b = gate[:, None, None]                     # align with bkgqs
    bias = _mask_bias(q_pos, kv_pos, causal=True, window=0)
    s = jnp.where(gate_b, s_fp, s_int) + bias[:, None, None]
    w = jax.nn.softmax(s, axis=-1)
    gf = gate_b.astype(w.dtype)
    out = (jnp.einsum("bkgqs,bskd->bqkgd", (w * gf).astype(v_fp.dtype),
                      v_fp, preferred_element_type=jnp.float32)
           + jnp.einsum("bkgqs,bskd->bqkgd",
                        (w * (1.0 - gf)).astype(v_int.dtype), v_int,
                        preferred_element_type=jnp.float32))
    return out.reshape(B, Cv, H, Dh).astype(q.dtype)


def gather_draft_view(cache, draft_tables, draft_base, pos, dtype):
    """Pre-gather the sparse self-draft view ONCE per speculative round.

    ``cache`` is a whole pool SEGMENT (leaves carry the stacked layer
    axis L); ``draft_tables``/``draft_base`` (B, NDt) name each row's
    sink + recent blocks and their original table indices (-1 = pad).
    Positions stay truthful because K/V were encoded in place: entry e
    covers [draft_base[b, e] * bs, ...).  Returns per-layer dense K/V
    (L, B, NDt*bs, Hkv, Dh) plus shared key positions (B, NDt*bs); view
    slots at or past the round start ``pos`` (B,) are masked out — they
    hold stale bits, and the round's own tokens attend each other
    through the draft scratch instead (``attn_draft_view``).

    This gather is what keeps the draft loop off the big pool: a plain
    decode step carries the whole pool through the layer scan — a
    pool-sized slice + copy per layer per token — while the draft pays
    one gather here and then scans over view + scratch leaves orders of
    magnitude smaller.

    int8 pools dequantize the gather and overlay the row's fp ring on
    entries whose base block falls in the decode recency window; the
    ring is clean at round start because drafts never touch the pool.
    jnp-only by design: draft K/V are approximations that verification
    rewrites, so the drafter can never affect output tokens and has no
    kernel twin to keep in lockstep."""
    B, NDt = draft_tables.shape
    L, _, bs, Hkv, Dh = cache["k"].shape
    p = pos.astype(jnp.int32)
    base = draft_base.astype(jnp.int32)              # (B, NDt)
    if is_quant_cache(cache):
        k = dequantize_vectors_jnp(cache["k"][:, draft_tables],
                                   cache["k_scale"][:, draft_tables], dtype)
        v = dequantize_vectors_jnp(cache["v"][:, draft_tables],
                                   cache["v_scale"][:, draft_tables], dtype)
        R = cache["k_tail"].shape[2] // bs
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        recent = ((base >= 0) & (base <= (p // bs)[:, None])
                  & (base > (p // bs)[:, None] - R))  # (B, NDt)
        ring_k = cache["k_tail"].reshape(
            L, B, R, bs, Hkv, Dh)[:, rows, base % R]
        ring_v = cache["v_tail"].reshape(
            L, B, R, bs, Hkv, Dh)[:, rows, base % R]
        sel = recent[None, :, :, None, None, None]
        k = jnp.where(sel, ring_k.astype(dtype), k)
        v = jnp.where(sel, ring_v.astype(dtype), v)
    else:
        k = cache["k"][:, draft_tables]          # (L, B, NDt, bs, Hkv, Dh)
        v = cache["v"][:, draft_tables]
    k = k.reshape(L, B, NDt * bs, Hkv, Dh).astype(dtype)
    v = v.reshape(L, B, NDt * bs, Hkv, Dh).astype(dtype)
    j = jnp.arange(bs, dtype=jnp.int32)
    kv_pos = jnp.where(base[:, :, None] >= 0,
                       base[:, :, None] * bs + j[None, None],
                       -1).reshape(B, NDt * bs)
    kv_pos = jnp.where(kv_pos < p[:, None], kv_pos, -1)
    return k, v, kv_pos


def attend_paged_prefill(q, k_chunk, v_chunk, cache, row, table_row, c0,
                         w_eff):
    """Reference chunked-prefill attention: the chunk's queries (1, C, H,
    Dh) at absolute positions [c0, c0 + C) attend their HISTORY (pool
    positions < ``w_eff``) through block table ``table_row`` and the
    chunk's own K/V (positions >= ``w_eff``) from the fresh fp operands —
    the chunk has not been sealed into the pool yet, so in-chunk
    attention is always full precision, like the staged prefill it
    replaces.  The table is explicit for the same mid-admission isolation
    reason as ``paged_prefill_write``.  int8 pools dequantize the history
    gather and read the last R HISTORY blocks (ending at the newest
    history block hb) from the row's fp ring tail, mirroring the
    decode-side recency gate — the ring still holds exactly those blocks
    because sealing happens after attention."""
    _, C, H, Dh = q.shape
    tbl = table_row                              # (NBt,)
    NBt = tbl.shape[0]
    bs = cache["k"].shape[1]
    if is_quant_cache(cache):
        k = dequantize_vectors_jnp(cache["k"][tbl], cache["k_scale"][tbl],
                                   q.dtype)
        v = dequantize_vectors_jnp(cache["v"][tbl], cache["v_scale"][tbl],
                                   q.dtype)
        R = cache["k_tail"].shape[1] // bs
        hb = (w_eff - 1) // bs                   # newest history block
        ti = jnp.arange(NBt, dtype=jnp.int32)
        recent = (ti <= hb) & (ti > hb - R)
        sel = recent[:, None, None, None]
        k = jnp.where(sel, cache["k_tail"][row].reshape(
            R, bs, *k.shape[2:])[ti % R].astype(q.dtype), k)
        v = jnp.where(sel, cache["v_tail"][row].reshape(
            R, bs, *v.shape[2:])[ti % R].astype(q.dtype), v)
    else:
        k = cache["k"][tbl]                      # (NBt, bs, Hkv, Dh)
        v = cache["v"][tbl]
    k = k.reshape(1, NBt * bs, *k.shape[2:])
    v = v.reshape(1, NBt * bs, *v.shape[2:])
    # history slots are valid below w_eff; chunk operand slots at/after it
    # (kv_pos -1 marks an invalid slot for _mask_bias)
    hist_pos = jnp.arange(NBt * bs, dtype=jnp.int32)
    hist_pos = jnp.where(hist_pos < w_eff, hist_pos, -1)
    chunk_pos = c0 + jnp.arange(C, dtype=jnp.int32)
    chunk_pos = jnp.where(chunk_pos >= w_eff, chunk_pos, -1)
    k = jnp.concatenate([k, k_chunk.astype(k.dtype)], axis=1)
    v = jnp.concatenate([v, v_chunk.astype(v.dtype)], axis=1)
    kv_pos = jnp.concatenate([hist_pos, chunk_pos])
    q_pos = c0 + jnp.arange(C, dtype=jnp.int32)
    return attend_direct(q, k, v, q_pos, kv_pos, causal=True)


def paged_prefill_write_packed(cache, k_new, v_new, rows, tables, c0s,
                               w_floors, valids, q_offs, seg_ids):
    """Ragged packed multi-admission prefill scatter: token t of the
    packed buffer (1, T, Hkv, Dh) belongs to segment ``seg_ids[t]`` and
    writes absolute position ``c0s[seg] + (t - q_offs[seg])`` of pool row
    ``rows[seg]`` through that segment's table row — the packed analogue
    of ``paged_prefill_write``, with every per-chunk scalar promoted to a
    per-segment vector.  Tokens past their segment's ``valids`` (chunk
    padding) or below its ``w_floors`` (host-promoted boundary remainder)
    route to the sentinel block; distinct segments write distinct blocks
    (the allocator never shares a non-sentinel block between admissions),
    so the one fused scatter has no cross-segment collisions.

    int8 pools dual-write each segment's last R blocks into ITS row's
    ring tail (per-segment newest block from c0 + n_valid); invalid ring
    writes route out of bounds and drop, exactly like the per-chunk
    path."""
    bs = cache["k"].shape[1]
    T = k_new.shape[1]
    t = jnp.arange(T, dtype=jnp.int32)
    seg = seg_ids.astype(jnp.int32)
    i = t - q_offs[seg]
    p = c0s[seg] + i
    valid = (i >= 0) & (i < valids[seg]) & (p >= w_floors[seg])
    blk = jnp.where(valid, tables[seg, p // bs], 0)
    off = p % bs
    if is_quant_cache(cache):
        kq, ks = _quantize_kv(k_new[0])
        vq, vs = _quantize_kv(v_new[0])
        R = cache["k_tail"].shape[1] // bs
        wb = (c0s + valids - 1) // bs        # per-seg newest sealed block
        ring_ok = valid & (p // bs > wb[seg] - R)
        ring = jnp.where(ring_ok, (p // bs) % R * bs + off, R * bs)
        return {
            "k": cache["k"].at[blk, off].set(kq),
            "v": cache["v"].at[blk, off].set(vq),
            "k_scale": cache["k_scale"].at[blk, off].set(ks),
            "v_scale": cache["v_scale"].at[blk, off].set(vs),
            "k_tail": cache["k_tail"].at[rows[seg], ring].set(k_new[0],
                                                              mode="drop"),
            "v_tail": cache["v_tail"].at[rows[seg], ring].set(v_new[0],
                                                              mode="drop"),
            "block_tables": cache["block_tables"],
        }
    return {
        "k": cache["k"].at[blk, off].set(k_new[0]),
        "v": cache["v"].at[blk, off].set(v_new[0]),
        "block_tables": cache["block_tables"],
    }


def attend_paged_prefill_packed(q, k_chunk, v_chunk, cache, rows, tables,
                                c0s, w_floors, q_offs, seg_ids):
    """Reference ragged packed multi-admission prefill attention: the
    packed buffer's T queries (1, T, H, Dh) each attend their OWN
    segment's history (pool positions < that segment's w_eff) through its
    table row plus the same-segment slice of the packed chunk operands
    (positions >= w_eff) — other segments' keys are masked out, so the
    result is bit-for-bit ``attend_paged_prefill`` run per segment.
    Treats each packed token as its own batch row for ``attend_direct``:
    per-token history is the segment gather, per-token chunk validity is
    the segment-equality mask.  int8 pools dequantize the history gather
    and read each segment's last R history blocks from ITS row's fp ring
    tail (per-segment w_eff recency gate), like the per-chunk
    reference."""
    _, T, H, Dh = q.shape
    S, NBt = tables.shape
    bs = cache["k"].shape[1]
    Hkv = k_chunk.shape[2]
    w_effs = jnp.maximum(w_floors, c0s)                  # (S,)
    t = jnp.arange(T, dtype=jnp.int32)
    seg = seg_ids.astype(jnp.int32)                      # (T,)
    q_pos = c0s[seg] + (t - q_offs[seg])                 # (T,)
    if is_quant_cache(cache):
        k_hist = dequantize_vectors_jnp(cache["k"][tables],
                                        cache["k_scale"][tables], q.dtype)
        v_hist = dequantize_vectors_jnp(cache["v"][tables],
                                        cache["v_scale"][tables], q.dtype)
        R = cache["k_tail"].shape[1] // bs
        hb = (w_effs - 1) // bs              # per-seg newest history block
        ti = jnp.arange(NBt, dtype=jnp.int32)
        recent = (ti[None] <= hb[:, None]) & (ti[None] > hb[:, None] - R)
        tail_k = cache["k_tail"][rows].reshape(S, R, bs, Hkv, Dh)[:, ti % R]
        tail_v = cache["v_tail"][rows].reshape(S, R, bs, Hkv, Dh)[:, ti % R]
        sel = recent[:, :, None, None, None]
        k_hist = jnp.where(sel, tail_k.astype(q.dtype), k_hist)
        v_hist = jnp.where(sel, tail_v.astype(q.dtype), v_hist)
    else:
        k_hist = cache["k"][tables]          # (S, NBt, bs, Hkv, Dh)
        v_hist = cache["v"][tables]
    k_hist = k_hist.reshape(S, NBt * bs, Hkv, Dh).astype(q.dtype)
    v_hist = v_hist.reshape(S, NBt * bs, Hkv, Dh).astype(q.dtype)
    hist_pos = jnp.arange(NBt * bs, dtype=jnp.int32)
    hp = jnp.where(hist_pos[None] < w_effs[:, None], hist_pos[None], -1)
    # each token's keys: its segment's history + the whole packed chunk,
    # with cross-segment (and below-w_eff) chunk slots masked to -1
    cp = jnp.where((seg[None, :] == seg[:, None])
                   & (q_pos[None, :] >= w_effs[seg][:, None]),
                   q_pos[None, :], -1)                   # (T, T)
    k_all = jnp.concatenate(
        [k_hist[seg],
         jnp.broadcast_to(k_chunk[0][None].astype(q.dtype),
                          (T, T, Hkv, Dh))], axis=1)
    v_all = jnp.concatenate(
        [v_hist[seg],
         jnp.broadcast_to(v_chunk[0][None].astype(q.dtype),
                          (T, T, Hkv, Dh))], axis=1)
    kv_pos = jnp.concatenate([hp[seg], cp], axis=1)      # (T, NBt*bs + T)
    out = attend_direct(q[0][:, None], k_all, v_all, q_pos[:, None],
                        kv_pos, causal=True)
    return out.reshape(1, T, H, Dh)


def _paged_gather_dequant(cache, dtype):
    """int8 pool -> per-row dense K/V (B, NBt*bs, Hkv, Dh): gather through
    the tables with dequant fused, then overlay the row's fp ring tail on
    its most recent ``fp_tail_blocks`` blocks."""
    tbl = cache["block_tables"]
    B, NBt = tbl.shape
    bs = cache["k"].shape[1]
    R = cache["k_tail"].shape[1] // bs
    k = dequantize_vectors_jnp(cache["k"][tbl], cache["k_scale"][tbl], dtype)
    v = dequantize_vectors_jnp(cache["v"][tbl], cache["v_scale"][tbl], dtype)
    # ring slot ti % R holds block ti's fp values for the last R blocks a
    # row progressed through; older slots are stale, so gate on recency at
    # attention time (the caller masks positions > pos regardless)
    ti = jnp.arange(NBt, dtype=jnp.int32)
    tail_k = cache["k_tail"].reshape(B, R, bs, *k.shape[3:])[:, ti % R]
    tail_v = cache["v_tail"].reshape(B, R, bs, *v.shape[3:])[:, ti % R]
    return k, v, tail_k, tail_v


def attend_paged(q, cache, pos):
    """Reference paged decode attention: gather K/V through the block
    table, mask by implicit positions.  q (B,1,H,Dh); pos (B,).  int8
    pools dequantize in the gather and read the most recent
    ``fp_tail_blocks`` blocks from the row's fp ring tail instead."""
    B = q.shape[0]
    NBt = cache["block_tables"].shape[1]
    bs = cache["k"].shape[1]
    p = pos.astype(jnp.int32)
    if is_quant_cache(cache):
        k, v, tail_k, tail_v = _paged_gather_dequant(cache, q.dtype)
        R = cache["k_tail"].shape[1] // bs
        ti = jnp.arange(NBt, dtype=jnp.int32)
        recent = (ti[None] <= (p // bs)[:, None]) & \
                 (ti[None] > (p // bs)[:, None] - R)       # (B, NBt)
        sel = recent[:, :, None, None, None]
        k = jnp.where(sel, tail_k, k)
        v = jnp.where(sel, tail_v, v)
    else:
        k = cache["k"][cache["block_tables"]]    # (B, NBt, bs, Hkv, Dh)
        v = cache["v"][cache["block_tables"]]
    k = k.reshape(B, NBt * bs, *k.shape[3:])
    v = v.reshape(B, NBt * bs, *v.shape[3:])
    kv_pos = jnp.arange(NBt * bs, dtype=jnp.int32)
    return attend_direct(q, k, v, p[:, None], kv_pos, causal=True)


def cache_write_batched(cache, k_new, v_new, pos):
    """Per-row scatter for the slot pool: row b writes its ``n`` new
    keys/values at absolute positions [pos[b], pos[b] + n); requires the
    per-slot layout (``slot_pos`` (B, C)).  Ring-wraps per row."""
    B, n = k_new.shape[0], k_new.shape[1]
    C = cache["k"].shape[1]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    p = pos.astype(jnp.int32)[:, None] + jnp.arange(n, dtype=jnp.int32)
    slots = p % C
    if is_quant_cache(cache):
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        return {
            "k": cache["k"].at[rows, slots].set(kq),
            "v": cache["v"].at[rows, slots].set(vq),
            "k_scale": cache["k_scale"].at[rows, slots].set(ks),
            "v_scale": cache["v_scale"].at[rows, slots].set(vs),
            "slot_pos": cache["slot_pos"].at[rows, slots].set(p),
        }
    return {
        "k": cache["k"].at[rows, slots].set(k_new),
        "v": cache["v"].at[rows, slots].set(v_new),
        "slot_pos": cache["slot_pos"].at[rows, slots].set(p),
    }


def attend_cache(cfg: ModelConfig, q, cache, q_pos, *, window=0, rt=None):
    """Attention of q against everything valid in the cache."""
    if is_quant_cache(cache):
        k, v = dequantize_cache(cache, q.dtype)
    else:
        k, v = cache["k"], cache["v"]
    use_chunked = q.shape[1] * k.shape[1] > 1 << 22
    if use_chunked:
        return attend_chunked(q, k, v, q_pos, cache["slot_pos"],
                              causal=True, window=window, ordered=False)
    return attend_direct(q, k, v, q_pos, cache["slot_pos"],
                         causal=True, window=window)


# ---------------------------------------------------------------------------
# full attention block entry points
# ---------------------------------------------------------------------------
def attn_prefill(cfg: ModelConfig, p, x, *, start_pos=0, cache=None,
                 window=0, rt=None):
    """Prefill S tokens starting at absolute position ``start_pos``.

    With ``cache`` given (recycled prefix!), new K/V are written into it and
    attention runs against the cache (prefix + new); otherwise attention is
    self-contained.  Returns (out, cache).

    A *paged* cache takes the chunked-admission path: ``start_pos`` is the
    5-tuple ``(row, table_row, chunk_start, w_floor, n_valid)`` (traced
    scalars plus the admitting row's (NBt,) block table; ``w_floor`` is
    the first position the chunk may write — above ``chunk_start`` when a
    host promotion pre-uploaded the boundary block) and the chunk's K/V
    are written straight into pool blocks — no staging cache, no
    gather/scatter round-trip (see ``models.prefill_paged``).
    """
    if cache is not None and is_paged_cache(cache):
        if not (isinstance(start_pos, tuple) and len(start_pos) == 5):
            raise TypeError(
                "paged-cache prefill goes through models.prefill_paged, "
                "which passes start_pos as (row, table_row, chunk_start, "
                f"w_floor, n_valid); got {start_pos!r}")
        row, table_row, c0, w_floor, n_valid = start_pos
        return _attn_prefill_paged(cfg, p, x, cache, row, table_row, c0,
                                   w_floor, n_valid, rt=rt)
    B, S, _ = x.shape
    positions = start_pos + jnp.arange(S, dtype=jnp.int32)
    q, k, v = project_qkv(cfg, p, x, positions)
    if cache is not None:
        cache = cache_write(cache, k, v, start_pos)
        if rt is not None and rt.use_pallas:
            out = _pallas_prefill(cfg, q, cache, positions, window, rt)
        else:
            out = attend_cache(cfg, q, cache, positions, window=window, rt=rt)
    else:
        if rt is not None and rt.use_pallas:
            out = _pallas_self(cfg, q, k, v, positions, window, rt)
        else:
            fn = attend_chunked if S * S > 1 << 22 else attend_direct
            out = fn(q, k, v, positions, positions, causal=True, window=window)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def _attn_prefill_paged(cfg: ModelConfig, p, x, cache, row, table_row, c0,
                        w_floor, n_valid, *, rt=None):
    """One chunk of a paged-native prefill: x (1, C, d) at absolute
    positions [c0, c0 + C) of pool row ``row`` (positions >= c0 + n_valid
    are padding).  The chunk attends history through the block table and
    itself from its fresh fp projections, THEN seals its K/V into the
    pool — so in-chunk attention is exact even for int8 pools, and the fp
    ring tail the history gate reads is still the pre-chunk state.  The
    staging round-trip of the old admission path does not exist here."""
    B, C, _ = x.shape
    positions = c0 + jnp.arange(C, dtype=jnp.int32)
    q, k, v = project_qkv(cfg, p, x, positions)
    w_eff = jnp.maximum(w_floor, c0)
    ax = paged_tp_axis(rt, cache)
    if ax is not None:
        return _tp_prefill_paged(cfg, p, q, k, v, cache, row, table_row,
                                 c0, w_eff, w_floor, n_valid, rt, ax)
    if rt is not None and rt.use_pallas:
        out = _pallas_prefill_paged(cfg, q, k, v, cache, row, table_row,
                                    c0, w_eff, rt)
    else:
        out = attend_paged_prefill(q, k, v, cache, row, table_row, c0,
                                   w_eff)
    cache = paged_prefill_write(cache, k, v, row, table_row, c0, w_floor,
                                n_valid)
    out = out.reshape(B, C, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def attn_prefill_packed(cfg: ModelConfig, p, x, cache, rows, tables, c0s,
                        w_floors, valids, q_offs, seg_ids, *, rt=None):
    """Ragged packed multi-admission prefill sublayer: x (1, T, d) is
    EVERY pending admission's current chunk concatenated (segments
    bs-aligned; token t of segment ``seg_ids[t]`` sits at absolute
    position ``c0s[seg] + (t - q_offs[seg])`` of pool row
    ``rows[seg]``).  Same attend-before-seal order as the per-chunk
    path — in-chunk attention is exact even for int8 pools — but all
    segments share ONE attention dispatch and ONE fused pool scatter."""
    B, T, _ = x.shape
    t = jnp.arange(T, dtype=jnp.int32)
    seg = seg_ids.astype(jnp.int32)
    positions = (c0s[seg] + (t - q_offs[seg]))[None]     # (1, T)
    q, k, v = project_qkv(cfg, p, x, positions)
    ax = paged_tp_axis(rt, cache)
    if ax is not None:
        return _tp_prefill_packed(cfg, p, q, k, v, cache, rows, tables,
                                  c0s, w_floors, valids, q_offs, seg_ids,
                                  rt, ax)
    if rt is not None and rt.use_pallas:
        out = _pallas_prefill_packed(cfg, q, k, v, cache, rows, tables,
                                     c0s, w_floors, q_offs, seg_ids, rt)
    else:
        out = attend_paged_prefill_packed(q, k, v, cache, rows, tables,
                                          c0s, w_floors, q_offs, seg_ids)
    cache = paged_prefill_write_packed(cache, k, v, rows, tables, c0s,
                                       w_floors, valids, q_offs, seg_ids)
    out = out.reshape(B, T, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def attn_decode(cfg: ModelConfig, p, x, cache, pos, *, window=0, rt=None):
    """One-token decode: x (B, 1, d), absolute position ``pos``.

    ``pos`` scalar: every row is at the same position (single-request path).
    ``pos`` (B,): per-row positions over a per-slot pool (``slot_pos``
    (B, C)) — each row attends only to its own row's valid slots, which is
    what lets a continuous batch mix requests at different depths.

    A paged cache (``block_tables`` present) always takes the per-row
    path: each row gathers K/V through its own block table, so requests
    at different depths share physical prefix blocks."""
    pos = jnp.asarray(pos)
    if is_paged_cache(cache):
        return _attn_decode_paged(cfg, p, x, cache, pos, window=window,
                                  rt=rt)
    if pos.ndim:
        return _attn_decode_batched(cfg, p, x, cache, pos, window=window,
                                    rt=rt)
    positions = jnp.reshape(pos, (1,)).astype(jnp.int32)
    q, k, v = project_qkv(cfg, p, x, positions)
    cache = cache_write(cache, k, v, positions[0])
    if rt is not None and rt.use_pallas and not is_quant_cache(cache):
        out = _pallas_decode(cfg, q, cache, positions, window, rt)
    else:
        if is_quant_cache(cache):
            kc, vc = dequantize_cache(cache, q.dtype)
        else:
            kc, vc = cache["k"], cache["v"]
        out = attend_direct(q, kc, vc, positions,
                            cache["slot_pos"], causal=True, window=window)
    out = out.reshape(x.shape[0], 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def _attn_decode_batched(cfg: ModelConfig, p, x, cache, pos, *, window=0,
                         rt=None):
    """Slot-pool decode: x (B, 1, d), pos (B,), cache slot_pos (B, C)."""
    positions = pos.astype(jnp.int32)[:, None]          # (B, 1)
    q, k, v = project_qkv(cfg, p, x, positions)
    cache = cache_write_batched(cache, k, v, pos)
    if rt is not None and rt.use_pallas and not is_quant_cache(cache):
        out = _pallas_decode_batched(cfg, q, cache, pos, window, rt)
    else:
        if is_quant_cache(cache):
            kc, vc = dequantize_cache(cache, q.dtype)
        else:
            kc, vc = cache["k"], cache["v"]
        out = attend_direct(q, kc, vc, positions, cache["slot_pos"],
                            causal=True, window=window)
    out = out.reshape(x.shape[0], 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def _attn_decode_paged(cfg: ModelConfig, p, x, cache, pos, *, window=0,
                       rt=None):
    """Paged-pool decode: x (B, 1, d), pos (B,), cache is a shared block
    pool + per-row block tables (see ``init_paged_kv_cache``)."""
    if window:
        raise NotImplementedError("paged pool has no ring semantics; "
                                  "windowed decode stays on the slot pool")
    positions = pos.astype(jnp.int32)[:, None]          # (B, 1)
    q, k, v = project_qkv(cfg, p, x, positions)
    ax = paged_tp_axis(rt, cache)
    if ax is not None:
        return _tp_decode_paged(cfg, p, q, k, v, cache, pos, rt, ax)
    cache = paged_cache_write(cache, k, v, pos)
    if rt is not None and rt.use_pallas:
        out = _pallas_decode_paged(cfg, q, cache, pos, rt)
    else:
        out = attend_paged(q, cache, pos)
    out = out.reshape(x.shape[0], 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def attn_verify(cfg: ModelConfig, p, x, cache, c0s, n_valid, act, *,
                rt=None):
    """Speculative-verify sublayer: x (B, Cv, d) is every row's pending
    token plus its gamma draft tokens at positions [c0s[b], c0s[b] + Cv).
    The bundle attends full history through the device block tables and
    itself from its fresh projections, THEN seals K/V into the
    speculatively reserved blocks (``paged_verify_write``) — the same
    attend-before-seal order as chunked prefill, so int8 pools see exact
    fp values for the bundle.  Rows with act == 0 and padding positions
    scribble the sentinel block."""
    B, Cv, _ = x.shape
    c0s = jnp.asarray(c0s, jnp.int32)
    positions = c0s[:, None] + jnp.arange(Cv, dtype=jnp.int32)
    q, k, v = project_qkv(cfg, p, x, positions)
    ax = paged_tp_axis(rt, cache)
    if ax is not None:
        return _tp_verify_paged(cfg, p, q, k, v, cache, c0s, n_valid, act,
                                rt, ax)
    if rt is not None and rt.use_pallas:
        out = _pallas_verify_paged(cfg, q, k, v, cache, c0s, rt)
    else:
        out = attend_paged_verify(q, k, v, cache, c0s)
    # the ring snapshot rides the cache only into attention; the written
    # cache returns to the plain pool structure
    cache = {kk: vv for kk, vv in cache.items()
             if kk not in ("k_tail_snap", "v_tail_snap")}
    cache = paged_verify_write(cache, k, v, c0s, n_valid, act)
    out = out.reshape(B, Cv, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], cache


def attn_draft_view(cfg: ModelConfig, p, x, cache, qpos, vpos, *, rt=None):
    """Draft-bundle attention sublayer over a pre-gathered sparse view:
    x (B, G, d) holds the round's CURRENT draft guesses at positions
    ``qpos`` (B, G), attending the view (``cache["vk"]/["vv"]`` with key
    positions ``vpos``) plus the bundle itself from its fresh
    projections — the verify staircase, minus the pool.  Nothing is
    read from or written to any persistent cache: every fixed-point
    sweep recomputes the bundle's K/V from the refined guesses, and
    verification re-encodes the round's positions with full-context
    values, so drafts only decide what gets PROPOSED."""
    positions = qpos.astype(jnp.int32)               # (B, G)
    q, kn, vn = project_qkv(cfg, p, x, positions)
    k = jnp.concatenate([cache["vk"], kn], axis=1)
    v = jnp.concatenate([cache["vv"], vn], axis=1)
    kv_pos = jnp.concatenate([vpos, positions], axis=1)
    out = attend_direct(q, k, v, positions, kv_pos, causal=True)
    out = out.reshape(x.shape[0], x.shape[1],
                      cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], {}


# Cross attention (whisper decoder): no causal mask, static kv from encoder.
def init_cross_attention(cfg: ModelConfig, key, dtype):
    return init_attention(cfg, key, dtype, cross=True)


def cross_attend(cfg: ModelConfig, p, x, enc_k, enc_v, rt=None):
    B, S, _ = x.shape
    h, dh = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"] + (p["bq"] if cfg.qkv_bias else 0)).reshape(B, S, h, dh)
    F = enc_k.shape[1]
    qpos = jnp.arange(S, dtype=jnp.int32)
    kpos = jnp.arange(F, dtype=jnp.int32)
    out = attend_direct(q, enc_k, enc_v, qpos, kpos, causal=False)
    return out.reshape(B, S, h * dh) @ p["wo"]


def cross_kv(cfg: ModelConfig, p, enc_out):
    """Precompute cross-attention K/V once per request (cached)."""
    B, F, _ = enc_out.shape
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    k = enc_out @ p["wk"]
    v = enc_out @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return k.reshape(B, F, hkv, dh), v.reshape(B, F, hkv, dh)


# ---------------------------------------------------------------------------
# Pallas dispatch (lazy import; interpret mode on CPU)
# ---------------------------------------------------------------------------
def _pallas_self(cfg, q, k, v, positions, window, rt):
    from repro.kernels import ops
    return ops.flash_attention(q, k, v, causal=True, window=window)


def _pallas_prefill(cfg, q, cache, positions, window, rt):
    # Cache-backed prefill keeps the jnp path (scatter-backed cache reads are
    # not yet a kernel); self-attention region uses the flash kernel.
    return attend_cache(cfg, q, cache, positions, window=window)


def _pallas_decode(cfg, q, cache, positions, window, rt):
    from repro.kernels import ops
    return ops.decode_attention(q, cache["k"], cache["v"], cache["slot_pos"],
                                positions[0], window=window)


def _pallas_decode_batched(cfg, q, cache, pos, window, rt):
    from repro.kernels import ops
    return ops.decode_attention_batched(
        q, cache["k"], cache["v"], cache["slot_pos"], pos, window=window)


def _pallas_prefill_paged(cfg, q, k_chunk, v_chunk, cache, row, table_row,
                          c0, w_eff, rt):
    from repro.kernels import ops
    if is_quant_cache(cache):
        return ops.paged_prefill_attention_quant(
            q, k_chunk, v_chunk, cache["k"], cache["v"],
            cache["k_scale"], cache["v_scale"],
            cache["k_tail"][row], cache["v_tail"][row],
            table_row, c0, w_eff)
    return ops.paged_prefill_attention(
        q, k_chunk, v_chunk, cache["k"], cache["v"], table_row, c0, w_eff)


def _pallas_prefill_packed(cfg, q, k_chunk, v_chunk, cache, rows, tables,
                           c0s, w_floors, q_offs, seg_ids, rt):
    """Build the per-query-tile [seg, c0, w_eff, qt0] descriptors from
    the per-segment vectors (segments are bs-aligned, so tile qt's
    segment is ``seg_ids[qt * bs]``) and dispatch the packed kernel."""
    from repro.kernels import ops
    bs = cache["k"].shape[1]
    tile_seg = seg_ids[::bs].astype(jnp.int32)           # (QT,)
    w_effs = jnp.maximum(w_floors, c0s)
    desc = jnp.stack([tile_seg, c0s[tile_seg], w_effs[tile_seg],
                      q_offs[tile_seg] // bs])
    if is_quant_cache(cache):
        return ops.paged_prefill_attention_packed_quant(
            q, k_chunk, v_chunk, cache["k"], cache["v"],
            cache["k_scale"], cache["v_scale"],
            cache["k_tail"][rows], cache["v_tail"][rows],
            tables, desc)
    return ops.paged_prefill_attention_packed(
        q, k_chunk, v_chunk, cache["k"], cache["v"], tables, desc)


def _pallas_verify_paged(cfg, q, k_chunk, v_chunk, cache, c0s, rt):
    from repro.kernels import ops
    if is_quant_cache(cache):
        return ops.paged_verify_attention_quant(
            q, k_chunk, v_chunk, cache["k"], cache["v"],
            cache["k_scale"], cache["v_scale"],
            cache["k_tail_snap"], cache["v_tail_snap"],
            cache["block_tables"], c0s)
    return ops.paged_verify_attention(
        q, k_chunk, v_chunk, cache["k"], cache["v"],
        cache["block_tables"], c0s)


def _pallas_decode_paged(cfg, q, cache, pos, rt):
    from repro.kernels import ops
    if is_quant_cache(cache):
        return ops.paged_decode_attention_quant(
            q, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
            cache["k_tail"], cache["v_tail"], cache["block_tables"], pos)
    return ops.paged_decode_attention(
        q, cache["k"], cache["v"], cache["block_tables"], pos)


# ---------------------------------------------------------------------------
# Tensor-parallel paged dispatch (PR 8): the decode/prefill/verify paged
# sublayers run under shard_map with the KV-head axis split over 'model'.
#
# Head-split softmax is shard-local — every head's statistics live entirely
# on the shard that owns it, so each shard runs the SAME attend code (jnp
# reference or Pallas kernel) on its local (Hkv/tp)-head view of the pool;
# the cross-'model' reduction is the output projection: each shard holds
# the wo rows of its own heads, computes a partial (B, S, d) product, and
# a psum across 'model' assembles the full sublayer output.  Block tables
# and scalars stay replicated, so the scalar-prefetch gather and the pool
# writes are untouched — the allocator never knows the pool is sharded.
# ---------------------------------------------------------------------------
def paged_tp_axis(rt, cache):
    """The mesh axis splitting paged KV heads, or None (replication
    fallback — same ``kv_heads % tp`` rule as ``sharding.cache_shardings``
    and ``sharding.paged_pool_shardings``)."""
    if rt is None or rt.mesh is None or not rt.model_axes:
        return None
    ax = rt.model_axes[-1]
    if ax not in rt.mesh.shape or rt.mesh.shape[ax] <= 1:
        return None
    hkv = cache["k"].shape[-2]
    if hkv % rt.mesh.shape[ax]:
        return None
    return ax


def _paged_pool_specs(cache, ax):
    """shard_map PartitionSpecs for the paged pool leaves (KV heads on
    ``ax``; block tables replicated), mirroring paged_pool_shardings."""
    from jax.sharding import PartitionSpec as P
    specs = {}
    for name, leaf in cache.items():
        nd = leaf.ndim
        spec = [None] * nd
        if name in ("k", "v", "k_tail", "v_tail",
                    "k_tail_snap", "v_tail_snap"):
            spec[nd - 2] = ax
        elif name in ("k_scale", "v_scale"):
            spec[nd - 1] = ax
        specs[name] = P(*spec)
    return specs


def _shard_paged(body, rt, in_specs, out_specs):
    return jax.shard_map(body, mesh=rt.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _tp_decode_paged(cfg, p, q, k, v, cache, pos, rt, ax):
    from jax.sharding import PartitionSpec as P
    hs = P(None, None, ax, None)
    cs = _paged_pool_specs(cache, ax)

    def body(wo, q, k, v, cache, pos):
        cache = paged_cache_write(cache, k, v, pos)
        if rt.use_pallas:
            out = _pallas_decode_paged(cfg, q, cache, pos, rt)
        else:
            out = attend_paged(q, cache, pos)
        out = out.reshape(out.shape[0], 1, -1)
        y = jax.lax.psum(out @ wo, ax)
        return y, cache

    f = _shard_paged(body, rt,
                     in_specs=(P(ax, None), hs, hs, hs, cs, P(None)),
                     out_specs=(P(None, None, None), cs))
    return f(p["wo"], q, k, v, cache, pos)


def _tp_prefill_paged(cfg, p, q, k, v, cache, row, table_row, c0, w_eff,
                      w_floor, n_valid, rt, ax):
    from jax.sharding import PartitionSpec as P
    hs = P(None, None, ax, None)
    cs = _paged_pool_specs(cache, ax)
    s = P()

    def body(wo, q, k, v, cache, row, table_row, c0, w_eff, w_floor,
             n_valid):
        if rt.use_pallas:
            out = _pallas_prefill_paged(cfg, q, k, v, cache, row, table_row,
                                        c0, w_eff, rt)
        else:
            out = attend_paged_prefill(q, k, v, cache, row, table_row, c0,
                                       w_eff)
        cache = paged_prefill_write(cache, k, v, row, table_row, c0,
                                    w_floor, n_valid)
        out = out.reshape(out.shape[0], out.shape[1], -1)
        y = jax.lax.psum(out @ wo, ax)
        return y, cache

    f = _shard_paged(body, rt,
                     in_specs=(P(ax, None), hs, hs, hs, cs, s, P(None),
                               s, s, s, s),
                     out_specs=(P(None, None, None), cs))
    return f(p["wo"], q, k, v, cache, row, table_row, c0, w_eff, w_floor,
             n_valid)


def _tp_prefill_packed(cfg, p, q, k, v, cache, rows, tables, c0s, w_floors,
                       valids, q_offs, seg_ids, rt, ax):
    from jax.sharding import PartitionSpec as P
    hs = P(None, None, ax, None)
    cs = _paged_pool_specs(cache, ax)

    def body(wo, q, k, v, cache, rows, tables, c0s, w_floors, valids,
             q_offs, seg_ids):
        if rt.use_pallas:
            out = _pallas_prefill_packed(cfg, q, k, v, cache, rows, tables,
                                         c0s, w_floors, q_offs, seg_ids,
                                         rt)
        else:
            out = attend_paged_prefill_packed(q, k, v, cache, rows, tables,
                                              c0s, w_floors, q_offs,
                                              seg_ids)
        cache = paged_prefill_write_packed(cache, k, v, rows, tables, c0s,
                                           w_floors, valids, q_offs,
                                           seg_ids)
        out = out.reshape(out.shape[0], out.shape[1], -1)
        y = jax.lax.psum(out @ wo, ax)
        return y, cache

    f = _shard_paged(body, rt,
                     in_specs=(P(ax, None), hs, hs, hs, cs, P(None),
                               P(None, None), P(None), P(None), P(None),
                               P(None), P(None)),
                     out_specs=(P(None, None, None), cs))
    return f(p["wo"], q, k, v, cache, rows, tables, c0s, w_floors, valids,
             q_offs, seg_ids)


def _tp_verify_paged(cfg, p, q, k, v, cache, c0s, n_valid, act, rt, ax):
    from jax.sharding import PartitionSpec as P
    hs = P(None, None, ax, None)
    cs = _paged_pool_specs(cache, ax)

    def body(wo, q, k, v, cache, c0s, n_valid, act):
        if rt.use_pallas:
            out = _pallas_verify_paged(cfg, q, k, v, cache, c0s, rt)
        else:
            out = attend_paged_verify(q, k, v, cache, c0s)
        cache = {kk: vv for kk, vv in cache.items()
                 if kk not in ("k_tail_snap", "v_tail_snap")}
        cache = paged_verify_write(cache, k, v, c0s, n_valid, act)
        out = out.reshape(out.shape[0], out.shape[1], -1)
        y = jax.lax.psum(out @ wo, ax)
        return y, cache

    cs_out = {kk: ss for kk, ss in cs.items()
              if kk not in ("k_tail_snap", "v_tail_snap")}
    f = _shard_paged(body, rt,
                     in_specs=(P(ax, None), hs, hs, hs, cs, P(None), P(),
                               P(None)),
                     out_specs=(P(None, None, None), cs_out))
    return f(p["wo"], q, k, v, cache, c0s, n_valid, act)
