"""Jit'd wrappers around the Pallas kernels (the model-facing surface)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.decode_attention import (decode_attention_batched
                                            as _decode_batched)
from repro.kernels.decode_attention import (paged_decode_attention
                                            as _decode_paged)
from repro.kernels.decode_attention import (paged_decode_attention_quant
                                            as _decode_paged_quant)
from repro.kernels.prefill_attention import (paged_prefill_attention
                                             as _prefill_paged)
from repro.kernels.prefill_attention import (paged_prefill_attention_quant
                                             as _prefill_paged_quant)
from repro.kernels.prefill_attention import (paged_prefill_attention_packed
                                             as _prefill_packed)
from repro.kernels.prefill_attention import (
    paged_prefill_attention_packed_quant as _prefill_packed_quant)
from repro.kernels.verify_attention import (paged_verify_attention
                                            as _verify_paged)
from repro.kernels.verify_attention import (paged_verify_attention_quant
                                            as _verify_paged_quant)
from repro.kernels.rwkv6_wkv import rwkv6_wkv as _wkv
from repro.kernels.rglru_scan import rglru_scan as _rglru


def interpret_mode(interpret=None) -> bool:
    """Whether a kernel runs in Pallas interpret mode.

    Interpret mode exists for the CPU backend only, where the tests run
    the kernels against their jnp oracles.  ``None`` decides from the
    backend; asking to interpret on an accelerator is an error, because
    there the kernels must compile through Mosaic.  Compiling for a
    described (not attached) TPU from a CPU process passes False."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"Pallas interpret mode requested on the "
            f"{jax.default_backend()!r} backend; kernels run compiled "
            "there")
    return bool(interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_start",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_start=0,
                    block_q=128, block_k=128, interpret=None):
    bq = min(block_q, q.shape[1])
    while q.shape[1] % bq:
        bq //= 2
    bk = min(block_k, k.shape[1])
    while k.shape[1] % bk:
        bk //= 2
    return _flash(q, k, v, causal=causal, window=window, q_start=q_start,
                  block_q=bq, block_k=bk, interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window=0,
                     block_k=256, interpret=None):
    bk = min(block_k, k_cache.shape[1])
    while k_cache.shape[1] % bk:
        bk //= 2
    return _decode(q, k_cache, v_cache, slot_pos, pos, window=window,
                   block_k=bk, interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention_batched(q, k_cache, v_cache, slot_pos, pos, *, window=0,
                             block_k=256, interpret=None):
    """Per-row (continuous-batching) decode: slot_pos (B,C), pos (B,)."""
    bk = min(block_k, k_cache.shape[1])
    while k_cache.shape[1] % bk:
        bk //= 2
    return _decode_batched(q, k_cache, v_cache, slot_pos, pos, window=window,
                           block_k=bk, interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, *,
                           interpret=None):
    """Block-table (paged pool) decode: pools (NB, bs, Hkv, D) shared by
    all rows; block_tables (B, NBt) scalar-prefetched so the kernel
    gathers each row's K/V blocks through its table; pos (B,)."""
    return _decode_paged(q, k_pool, v_pool, block_tables, pos,
                         interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_quant(q, k_pool, v_pool, k_scale, v_scale,
                                 k_tail, v_tail, block_tables, pos, *,
                                 interpret=None):
    """int8 block-table decode with the dequant fused into the table
    gather: pools (NB, bs, Hkv, D) int8 + per-vector f32 scales; the
    row's most recent blocks come from its fp ring tail (B, R*bs, Hkv, D)
    instead of the int8 pool."""
    return _decode_paged_quant(q, k_pool, v_pool, k_scale, v_scale,
                               k_tail, v_tail, block_tables, pos,
                               interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q, k_chunk, v_chunk, k_pool, v_pool, table_row,
                            c0, w_eff, *, interpret=None):
    """Chunked-prefill attention: q / chunk K/V (1, C, H|Hkv, D) is one
    fixed-size admission chunk; history (< w_eff) is gathered through the
    scalar-prefetched block table, the chunk itself from the fp operands
    (it has not been sealed to the pool yet); c0 / w_eff are traced
    scalars, so ONE compiled executable serves every suffix length."""
    return _prefill_paged(q, k_chunk, v_chunk, k_pool, v_pool, table_row,
                          c0, w_eff, interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                                  k_scale, v_scale, k_tail_row, v_tail_row,
                                  table_row, c0, w_eff, *, interpret=None):
    """int8 chunked prefill with the dequant fused into the history table
    gather; the last R history blocks come from the row's fp ring tail
    (R*bs, Hkv, D) instead of the int8 pool, and the chunk's own K/V from
    its fp operands."""
    return _prefill_paged_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                                k_scale, v_scale, k_tail_row, v_tail_row,
                                table_row, c0, w_eff,
                                interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("chunk_tiles", "interpret"))
def paged_prefill_attention_packed(q, k_chunk, v_chunk, k_pool, v_pool,
                                   tables, desc, *, chunk_tiles=None,
                                   interpret=None):
    """Ragged packed multi-admission prefill: q / chunk K/V (1, T, H|Hkv,
    D) concatenate EVERY pending admission's current chunk (segments
    bs-aligned, T a bucket size); tables (S, NBt) are the per-segment
    block tables and desc (4, QT) the per-query-tile [seg, c0, w_eff,
    qt0] descriptors, both scalar-prefetched — so ONE compiled executable
    per (bucket, segment-count) shape serves any number of concurrent
    admissions at any depth."""
    return _prefill_packed(q, k_chunk, v_chunk, k_pool, v_pool, tables,
                           desc, chunk_tiles=chunk_tiles,
                           interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("chunk_tiles", "interpret"))
def paged_prefill_attention_packed_quant(q, k_chunk, v_chunk, k_pool,
                                         v_pool, k_scale, v_scale, k_tails,
                                         v_tails, tables, desc, *,
                                         chunk_tiles=None, interpret=None):
    """int8 ragged packed prefill with the dequant fused into the
    segment-table gather; each segment's last R history blocks come from
    its row's fp ring tail (S, R*bs, Hkv, D), gathered by the caller."""
    return _prefill_packed_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                                 k_scale, v_scale, k_tails, v_tails,
                                 tables, desc, chunk_tiles=chunk_tiles,
                                 interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(q, k_chunk, v_chunk, k_pool, v_pool,
                           block_tables, c0s, *, interpret=None):
    """Batched speculative-verify attention: every row's (Cv,)-token
    draft bundle attends history through its scalar-prefetched block
    table and the bundle itself from the fp operands; c0s (B,) are the
    per-row bundle starts (armed rows have no write floor)."""
    return _verify_paged(q, k_chunk, v_chunk, k_pool, v_pool,
                         block_tables, c0s,
                         interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                                 k_scale, v_scale, k_tails, v_tails,
                                 block_tables, c0s, *, interpret=None):
    """int8 batched verify with the dequant fused into the table gather;
    the per-QUERY recency gate reads fp history from each row's
    pre-round ring snapshot (B, R*bs, Hkv, D) instead of the live
    (draft-polluted) ring."""
    return _verify_paged_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                               k_scale, v_scale, k_tails, v_tails,
                               block_tables, c0s,
                               interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, w, u, s0, *, chunk=16, interpret=None):
    c = min(chunk, r.shape[1])
    while r.shape[1] % c:
        c //= 2
    return _wkv(r, k, v, w, u, s0, chunk=c,
                interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_scan(a, b, h0, *, chunk=64, block_w=512, interpret=None):
    return _rglru(a, b, h0, chunk=chunk, block_w=block_w,
                  interpret=interpret_mode(interpret))
