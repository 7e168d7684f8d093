"""Paged-native chunked prefill attention (the admission hot path).

The staged admission path ran the dense prefill over a full-capacity
staging cache: gather the resident prefix out of the pool, prefill the
suffix, scatter the result back into pool blocks — a device round-trip
per admission, and one compiled executable per distinct suffix length.
This kernel removes the round-trip: a fixed-size chunk of C query tokens
attends its *history* directly against the request's pool blocks,
gathered through the scalar-prefetched block table exactly like
``paged_decode_attention``, and its *own* K/V from the chunk's fresh fp
operands (flash-attention style) — sealing to the pool happens after,
so in-chunk attention is always full precision.

Structure: flash-style online softmax (same recurrence as
``flash_attention``) with grid = (kv_heads, table_entries + chunk_tiles);
the kv dimension is sequential so the (C*G, d) softmax state stays in
VMEM.  Tiles ti < NBt are history: pool block ``table[ti]``, implicit
positions ti*bs + j, valid iff < ``w_eff`` (the history/chunk boundary —
normally the chunk start, or the promoted depth when a host promotion
pre-uploaded a partial boundary block) and causally <= the query's
position.  Tiles ti >= NBt are the chunk itself: fp operand slice at
positions c0 + (ti - NBt)*bs + j, valid iff >= ``w_eff``.  Sentinel
(block 0) table entries beyond the written region are harmless: their
positions exceed ``w_eff``.

Because C is FIXED (block-aligned ``prefill_chunk``), ONE compiled
executable serves every admission regardless of suffix length — c0 and
w_eff arrive as scalar-prefetch operands, never as shape.

``paged_prefill_attention_quant`` is the int8-pool variant: the history
gather fuses the per-vector dequant, and the last ``R`` history blocks
(ending at the newest history block, derived from w_eff) are read from
the row's fp ring tail instead — the same recency gate the int8 decode
kernel applies, so chunked prefill and decode see one consistent view of
where full precision lives.

``paged_prefill_attention_packed{,_quant}`` generalize the chunked pair
to MANY concurrent admissions in ONE dispatch: every pending admission's
current chunk is concatenated into a single ragged ``[total_tokens]``
buffer (each segment bs-aligned, the whole buffer padded to one of a few
bucket sizes), per-SEGMENT block tables arrive as a (S, NBt) scalar
prefetch, and a per-QUERY-TILE descriptor (4, QT) of
``[seg, c0, w_eff, qt0]`` rows drives both the gather index maps and the
segment-masked online softmax.  The grid gains a query-tile axis
(Hkv, QT, NBt + chunk_tiles) with the kv axis innermost, so each query
tile keeps its own (bs*G, d) softmax state in VMEM; because segments are
bs-aligned, every query tile belongs to exactly ONE segment and the
per-tile output blocks are disjoint.  Chunk kv tiles index the packed
buffer at ``qt0 + j`` — tiles past the query's own (j > qt - qt0) are
fully masked by causality (their minimum key position exceeds the tile's
maximum query position), so no cross-segment leakage is possible even
though neighbouring segments are adjacent in the buffer.  Buffer bucket
sizes and the fixed segment count keep the compile count independent of
both suffix length AND the number of concurrent admissions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (check_shard_view, scale_column,
                                            scale_layout, split_history)

NEG_INF = -1e30


def _accumulate(ti, ntiles, s, v, o_ref, m_scr, l_scr, acc_scr):
    """One online-softmax step over a pre-masked score tile ``s``
    (C*G, bs) against values ``v`` (bs, d), with the normalized write on
    the last tile.  Shared by the fp and int8 kernels so the
    normalization that must stay in lockstep for fp-vs-int8 token
    equivalence lives in one place."""
    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ti == ntiles - 1)
    def _write():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def _tile_mask(ti, sc_ref, CG, bs, G, nbt):
    """(kv positions, validity) for tile ``ti``: history tiles hold
    implicit pool positions valid below w_eff; chunk tiles hold operand
    positions valid at/after it.  Causality against the query rows
    (query row r is token r // G at position c0 + r // G) applies to
    both."""
    c0, w_eff = sc_ref[0], sc_ref[1]
    j = jax.lax.broadcasted_iota(jnp.int32, (CG, bs), 1)
    qp = c0 + jax.lax.broadcasted_iota(jnp.int32, (CG, bs), 0) // G
    is_hist = ti < nbt
    kp = jnp.where(is_hist, ti * bs + j, c0 + (ti - nbt) * bs + j)
    return (kp <= qp) & split_history(is_hist, kp, w_eff)


def _paged_prefill_kernel(tbl_ref, sc_ref, q_ref, k_ref, v_ref, kc_ref,
                          vc_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                          bs, nbt, G, cb):
    """One (kv_head, tile) program: for history tiles the BlockSpec index
    map already resolved table entry ``ti`` to a pool block; for chunk
    tiles it selected the matching slice of the chunk's fp K/V."""
    ti = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # (C*G, d)
    is_hist = ti < nbt
    k = jnp.where(is_hist, k_ref[0, 0], kc_ref[0, 0]).astype(jnp.float32)
    v = jnp.where(is_hist, v_ref[0, 0], vc_ref[0, 0]).astype(jnp.float32)
    s = q @ k.T * scale                               # (C*G, bs)
    s = jnp.where(_tile_mask(ti, sc_ref, q.shape[0], bs, G, nbt),
                  s, NEG_INF)
    _accumulate(ti, nbt + cb, s, v, o_ref, m_scr, l_scr, acc_scr)


def _chunk_layouts(q, k_chunk, v_chunk, bs):
    """(1, C, H|Hkv, D) -> kernel layouts: q (Hkv, C*G, D) with query row
    r = (token r // G, group r % G); chunk K/V (Hkv, C/bs, bs, D)."""
    _, C, H, D = q.shape
    Hkv = k_chunk.shape[2]
    G = H // Hkv
    qr = (q.reshape(C, Hkv, G, D).transpose(1, 0, 2, 3)
          .reshape(Hkv, C * G, D))
    kcr = (k_chunk.reshape(C // bs, bs, Hkv, D).transpose(2, 0, 1, 3))
    vcr = (v_chunk.reshape(C // bs, bs, Hkv, D).transpose(2, 0, 1, 3))
    return qr, kcr, vcr


def paged_prefill_attention(q, k_chunk, v_chunk, k_pool, v_pool, table_row,
                            c0, w_eff, *, scale=None, interpret=True):
    """Chunked-prefill attention through a block table.

    q / k_chunk / v_chunk (1, C, H|Hkv, D): one admission chunk's roped
    projections at absolute positions [c0, c0 + C); pools
    (NB, bs, Hkv, D) shared by all requests; table_row (NBt,) int32 — the
    admitting request's block table (sentinel-0 padded); c0, w_eff scalar
    int32.  History (< w_eff) is read through the table; the chunk itself
    (>= w_eff) from the fp operands, so sealing K/V to the pool can
    happen AFTER attention.  Chunk padding queries produce garbage the
    caller discards.  Returns (1, C, H, D)."""
    _, C, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = table_row.shape[0]
    CB = C // bs
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr, kcr, vcr = _chunk_layouts(q, k_chunk, v_chunk, bs)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D)
    vr = v_pool.transpose(2, 0, 1, 3)
    sc = jnp.stack([jnp.asarray(c0, jnp.int32),
                    jnp.asarray(w_eff, jnp.int32)])

    def hist_ix(h, ti, tbl, sc, n=NBt):
        return (h, tbl[jnp.minimum(ti, n - 1)], 0, 0)

    def chunk_ix(h, ti, tbl, sc, n=NBt, c=CB):
        return (h, jnp.clip(ti - n, 0, c - 1), 0, 0)

    kernel = functools.partial(_paged_prefill_kernel, scale=scale, bs=bs,
                               nbt=NBt, G=G, cb=CB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block table + [c0, w_eff]
        grid=(Hkv, NBt + CB),
        in_specs=[
            pl.BlockSpec((1, C * G, D), lambda h, ti, tbl, sc: (h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
        ],
        out_specs=pl.BlockSpec((1, C * G, D),
                               lambda h, ti, tbl, sc: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, C * G, D), q.dtype),
        interpret=interpret,
    )(table_row.astype(jnp.int32), sc, qr, kr, vr, kcr, vcr)
    return (out.reshape(Hkv, C, G, D).transpose(1, 0, 2, 3)
            .reshape(1, C, H, D))


def _paged_prefill_kernel_quant(tbl_ref, sc_ref, q_ref, k_ref, v_ref,
                                ks_ref, vs_ref, kt_ref, vt_ref, kc_ref,
                                vc_ref, o_ref, m_scr, l_scr, acc_scr, *,
                                scale, bs, nbt, G, cb, rtail):
    """int8 variant: history K/V tiles arrive as int8 pool blocks plus
    their per-vector f32 scales (same table-lookup index map) with the
    dequant fused into the gather; the last ``rtail`` HISTORY blocks
    (ending at the newest history block hb, from w_eff) are read from the
    row's fp ring tail instead — attention runs before the chunk seals,
    so the ring still holds exactly those blocks.  Chunk tiles use the fp
    operands like the fp kernel."""
    ti = pl.program_id(1)
    h = pl.program_id(0)
    q = q_ref[0].astype(jnp.float32)                  # (C*G, d)
    k8 = k_ref[0, 0].astype(jnp.float32)              # (bs, d) int8 tile
    v8 = v_ref[0, 0].astype(jnp.float32)
    ks = scale_column(ks_ref, h, bs)                  # (bs, 1) f32 scales
    vs = scale_column(vs_ref, h, bs)
    kt = kt_ref[0, 0].astype(jnp.float32)             # (bs, d) fp ring tile
    vt = vt_ref[0, 0].astype(jnp.float32)
    kc = kc_ref[0, 0].astype(jnp.float32)             # (bs, d) fp chunk tile
    vc = vc_ref[0, 0].astype(jnp.float32)

    hb = (sc_ref[1] - 1) // bs                        # newest history block
    use_fp = (ti <= hb) & (ti > hb - rtail)           # scalar: ring block?
    is_hist = ti < nbt
    k = jnp.where(is_hist, jnp.where(use_fp, kt, k8 * ks), kc)
    v = jnp.where(is_hist, jnp.where(use_fp, vt, v8 * vs), vc)
    s = q @ k.T * scale
    s = jnp.where(_tile_mask(ti, sc_ref, q.shape[0], bs, G, nbt),
                  s, NEG_INF)
    _accumulate(ti, nbt + cb, s, v, o_ref, m_scr, l_scr, acc_scr)


def paged_prefill_attention_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                                  k_scale, v_scale, k_tail_row, v_tail_row,
                                  table_row, c0, w_eff, *, scale=None,
                                  interpret=True):
    """Fused-dequant chunked prefill: q / chunk K/V (1, C, H|Hkv, D); int8
    pools (NB, bs, Hkv, D) with f32 scales (NB, bs, Hkv); the admitting
    row's fp ring tail (R*bs, Hkv, D); table_row (NBt,); c0, w_eff
    scalars.  The table gather is unchanged from the fp kernel — only
    history tile contents differ (int8 + scale, or the fp ring slot for
    the last R history blocks).  Returns (1, C, H, D)."""
    _, C, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = table_row.shape[0]
    CB = C // bs
    R = k_tail_row.shape[0] // bs
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr, kcr, vcr = _chunk_layouts(q, k_chunk, v_chunk, bs)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D) int8
    vr = v_pool.transpose(2, 0, 1, 3)
    ksr = scale_layout(k_scale)                       # (NB, Hkv, bs) f32
    vsr = scale_layout(v_scale)
    ktr = (k_tail_row.reshape(R, bs, Hkv, D)          # (Hkv, R, bs, D)
           .transpose(2, 0, 1, 3))
    vtr = (v_tail_row.reshape(R, bs, Hkv, D)
           .transpose(2, 0, 1, 3))
    sc = jnp.stack([jnp.asarray(c0, jnp.int32),
                    jnp.asarray(w_eff, jnp.int32)])

    def hist_ix(h, ti, tbl, sc, n=NBt):
        return (h, tbl[jnp.minimum(ti, n - 1)], 0, 0)

    def hist_ix_s(h, ti, tbl, sc, n=NBt):
        return (tbl[jnp.minimum(ti, n - 1)], 0, 0)

    def ring_ix(h, ti, tbl, sc, r=R):
        return (h, ti % r, 0, 0)

    def chunk_ix(h, ti, tbl, sc, n=NBt, c=CB):
        return (h, jnp.clip(ti - n, 0, c - 1), 0, 0)

    kernel = functools.partial(_paged_prefill_kernel_quant, scale=scale,
                               bs=bs, nbt=NBt, G=G, cb=CB, rtail=R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block table + [c0, w_eff]
        grid=(Hkv, NBt + CB),
        in_specs=[
            pl.BlockSpec((1, C * G, D), lambda h, ti, tbl, sc: (h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, Hkv, bs), hist_ix_s),
            pl.BlockSpec((1, Hkv, bs), hist_ix_s),
            pl.BlockSpec((1, 1, bs, D), ring_ix),
            pl.BlockSpec((1, 1, bs, D), ring_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
        ],
        out_specs=pl.BlockSpec((1, C * G, D),
                               lambda h, ti, tbl, sc: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, C * G, D), q.dtype),
        interpret=interpret,
    )(table_row.astype(jnp.int32), sc, qr, kr, vr, ksr, vsr, ktr, vtr,
      kcr, vcr)
    return (out.reshape(Hkv, C, G, D).transpose(1, 0, 2, 3)
            .reshape(1, C, H, D))


# ---------------------------------------------------------------------------
# ragged packed multi-admission prefill
# ---------------------------------------------------------------------------
def _packed_tile_mask(qt, ti, desc_ref, BG, bs, G, nbt):
    """Validity for query tile ``qt`` against kv tile ``ti``: history
    tiles (< nbt) hold the tile's SEGMENT's pool positions, valid below
    its w_eff; chunk tiles hold the segment's packed-buffer positions at
    or after it.  Causality uses the tile's absolute query positions
    ``c0 + (qt - qt0) * bs + r // G``."""
    c0 = desc_ref[1, qt]
    w_eff = desc_ref[2, qt]
    qt0 = desc_ref[3, qt]
    j = jax.lax.broadcasted_iota(jnp.int32, (BG, bs), 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (BG, bs), 0)
    qp = c0 + (qt - qt0) * bs + r // G
    is_hist = ti < nbt
    kp = jnp.where(is_hist, ti * bs + j, c0 + (ti - nbt) * bs + j)
    return (kp <= qp) & split_history(is_hist, kp, w_eff)


def _paged_prefill_packed_kernel(tbl_ref, desc_ref, q_ref, k_ref, v_ref,
                                 kc_ref, vc_ref, o_ref, m_scr, l_scr,
                                 acc_scr, *, scale, bs, nbt, G, ntiles):
    """One (kv_head, query_tile, kv_tile) program: the BlockSpec index
    maps already resolved history tile ``ti`` through the tile's
    SEGMENT's table row, and chunk tile ``ti - nbt`` to packed-buffer
    tile ``qt0 + (ti - nbt)``; the mask keeps everything segment-local."""
    qt = pl.program_id(1)
    ti = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)                  # (bs*G, d)
    is_hist = ti < nbt
    k = jnp.where(is_hist, k_ref[0, 0], kc_ref[0, 0]).astype(jnp.float32)
    v = jnp.where(is_hist, v_ref[0, 0], vc_ref[0, 0]).astype(jnp.float32)
    s = q @ k.T * scale                               # (bs*G, bs)
    s = jnp.where(_packed_tile_mask(qt, ti, desc_ref, q.shape[0], bs, G,
                                    nbt), s, NEG_INF)
    _accumulate(ti, ntiles, s, v, o_ref, m_scr, l_scr, acc_scr)


def paged_prefill_attention_packed(q, k_chunk, v_chunk, k_pool, v_pool,
                                   tables, desc, *, scale=None,
                                   chunk_tiles=None, interpret=True):
    """Ragged packed multi-admission prefill attention.

    q / k_chunk / v_chunk (1, T, H|Hkv, D): EVERY pending admission's
    current chunk concatenated (each segment bs-aligned, T padded to a
    bucket size); pools (NB, bs, Hkv, D); tables (S, NBt) int32 — one
    block-table row per segment (sentinel rows for padding segments);
    desc (4, QT) int32 — per query tile ``[seg, c0, w_eff, qt0]`` where
    qt0 is the segment's first packed tile.  History (< w_eff) is read
    through the tile's segment's table; the segment's own chunk
    (>= w_eff) from the fp operands.  ``chunk_tiles`` bounds how many
    chunk kv tiles any one segment spans (defaults to all of them).
    Padding queries produce garbage the caller discards.
    Returns (1, T, H, D)."""
    _, T, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = tables.shape[1]
    QT = T // bs
    CB = chunk_tiles or QT
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr, kcr, vcr = _chunk_layouts(q, k_chunk, v_chunk, bs)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D)
    vr = v_pool.transpose(2, 0, 1, 3)

    def hist_ix(h, qt, ti, tbl, dsc, n=NBt):
        return (h, tbl[dsc[0, qt], jnp.minimum(ti, n - 1)], 0, 0)

    def chunk_ix(h, qt, ti, tbl, dsc, n=NBt, qtt=QT):
        return (h, jnp.clip(dsc[3, qt] + ti - n, 0, qtt - 1), 0, 0)

    def q_ix(h, qt, ti, tbl, dsc):
        return (h, qt, 0)

    kernel = functools.partial(_paged_prefill_packed_kernel, scale=scale,
                               bs=bs, nbt=NBt, G=G, ntiles=NBt + CB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # segment tables + tile descriptors
        grid=(Hkv, QT, NBt + CB),
        in_specs=[
            pl.BlockSpec((1, bs * G, D), q_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
        ],
        out_specs=pl.BlockSpec((1, bs * G, D), q_ix),
        scratch_shapes=[
            pltpu.VMEM((bs * G,), jnp.float32),
            pltpu.VMEM((bs * G,), jnp.float32),
            pltpu.VMEM((bs * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, T * G, D), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), desc.astype(jnp.int32), qr, kr, vr,
      kcr, vcr)
    return (out.reshape(Hkv, T, G, D).transpose(1, 0, 2, 3)
            .reshape(1, T, H, D))


def _paged_prefill_packed_kernel_quant(tbl_ref, desc_ref, q_ref, k_ref,
                                       v_ref, ks_ref, vs_ref, kt_ref,
                                       vt_ref, kc_ref, vc_ref, o_ref,
                                       m_scr, l_scr, acc_scr, *, scale, bs,
                                       nbt, G, ntiles, rtail):
    """int8 packed variant: history tiles arrive as int8 pool blocks plus
    scales (dequant fused into the segment-table gather); the last
    ``rtail`` HISTORY blocks of each tile's SEGMENT (ending at its newest
    history block hb, from its w_eff) come from that segment's fp ring
    tail — per-tile w_eff makes the recency gate per-segment, otherwise
    identical to the chunked quant kernel."""
    h = pl.program_id(0)
    qt = pl.program_id(1)
    ti = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)                  # (bs*G, d)
    k8 = k_ref[0, 0].astype(jnp.float32)              # (bs, d) int8 tile
    v8 = v_ref[0, 0].astype(jnp.float32)
    ks = scale_column(ks_ref, h, bs)                  # (bs, 1) f32 scales
    vs = scale_column(vs_ref, h, bs)
    kt = kt_ref[0, 0, 0].astype(jnp.float32)          # (bs, d) fp ring tile
    vt = vt_ref[0, 0, 0].astype(jnp.float32)
    kc = kc_ref[0, 0].astype(jnp.float32)             # (bs, d) fp chunk tile
    vc = vc_ref[0, 0].astype(jnp.float32)

    hb = (desc_ref[2, qt] - 1) // bs                  # seg's newest hist blk
    use_fp = (ti <= hb) & (ti > hb - rtail)
    is_hist = ti < nbt
    k = jnp.where(is_hist, jnp.where(use_fp, kt, k8 * ks), kc)
    v = jnp.where(is_hist, jnp.where(use_fp, vt, v8 * vs), vc)
    s = q @ k.T * scale
    s = jnp.where(_packed_tile_mask(qt, ti, desc_ref, q.shape[0], bs, G,
                                    nbt), s, NEG_INF)
    _accumulate(ti, ntiles, s, v, o_ref, m_scr, l_scr, acc_scr)


def paged_prefill_attention_packed_quant(q, k_chunk, v_chunk, k_pool,
                                         v_pool, k_scale, v_scale, k_tails,
                                         v_tails, tables, desc, *,
                                         scale=None, chunk_tiles=None,
                                         interpret=True):
    """Fused-dequant ragged packed prefill: int8 pools (NB, bs, Hkv, D)
    with f32 scales (NB, bs, Hkv); k_tails / v_tails (S, R*bs, Hkv, D) —
    each SEGMENT's row's fp ring tail, gathered by the caller; tables
    (S, NBt); desc (4, QT).  The gathers are unchanged from the fp packed
    kernel — only history tile contents differ (int8 + scale, or the
    segment's fp ring slot for its last R history blocks).
    Returns (1, T, H, D)."""
    _, T, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = tables.shape[1]
    QT = T // bs
    CB = chunk_tiles or QT
    S = k_tails.shape[0]
    R = k_tails.shape[1] // bs
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr, kcr, vcr = _chunk_layouts(q, k_chunk, v_chunk, bs)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D) int8
    vr = v_pool.transpose(2, 0, 1, 3)
    ksr = scale_layout(k_scale)                       # (NB, Hkv, bs) f32
    vsr = scale_layout(v_scale)
    ktr = (k_tails.reshape(S, R, bs, Hkv, D)          # (Hkv, S, R, bs, D)
           .transpose(3, 0, 1, 2, 4))
    vtr = (v_tails.reshape(S, R, bs, Hkv, D)
           .transpose(3, 0, 1, 2, 4))

    def hist_ix(h, qt, ti, tbl, dsc, n=NBt):
        return (h, tbl[dsc[0, qt], jnp.minimum(ti, n - 1)], 0, 0)

    def hist_ix_s(h, qt, ti, tbl, dsc, n=NBt):
        return (tbl[dsc[0, qt], jnp.minimum(ti, n - 1)], 0, 0)

    def ring_ix(h, qt, ti, tbl, dsc, r=R):
        return (h, dsc[0, qt], ti % r, 0, 0)

    def chunk_ix(h, qt, ti, tbl, dsc, n=NBt, qtt=QT):
        return (h, jnp.clip(dsc[3, qt] + ti - n, 0, qtt - 1), 0, 0)

    def q_ix(h, qt, ti, tbl, dsc):
        return (h, qt, 0)

    kernel = functools.partial(_paged_prefill_packed_kernel_quant,
                               scale=scale, bs=bs, nbt=NBt, G=G,
                               ntiles=NBt + CB, rtail=R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # segment tables + tile descriptors
        grid=(Hkv, QT, NBt + CB),
        in_specs=[
            pl.BlockSpec((1, bs * G, D), q_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, Hkv, bs), hist_ix_s),
            pl.BlockSpec((1, Hkv, bs), hist_ix_s),
            pl.BlockSpec((1, 1, 1, bs, D), ring_ix),
            pl.BlockSpec((1, 1, 1, bs, D), ring_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
            pl.BlockSpec((1, 1, bs, D), chunk_ix),
        ],
        out_specs=pl.BlockSpec((1, bs * G, D), q_ix),
        scratch_shapes=[
            pltpu.VMEM((bs * G,), jnp.float32),
            pltpu.VMEM((bs * G,), jnp.float32),
            pltpu.VMEM((bs * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, T * G, D), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), desc.astype(jnp.int32), qr, kr, vr, ksr,
      vsr, ktr, vtr, kcr, vcr)
    return (out.reshape(Hkv, T, G, D).transpose(1, 0, 2, 3)
            .reshape(1, T, H, D))
