"""Batched multi-token speculative verification attention.

Self-speculative decoding verifies a bundle of ``gamma`` draft tokens
(plus the pending token that produced them) in ONE dispatch: every row's
``Cv = gamma + 1`` query tokens at absolute positions [c0[b], c0[b] + Cv)
attend their full history through the row's scalar-prefetched block
table — exactly the chunked-prefill gather — and the bundle's own K/V
from the fresh fp operands (flash style, the bundle has not been sealed
yet).  The difference from ``paged_prefill_attention`` is batch shape:
prefill admits ONE row per dispatch with scalar (c0, w_eff); verify runs
EVERY speculating row at once with a per-row c0 vector, which is what
makes speculation pay at batch > 1 (2 dispatches per round regardless of
batch size).  Verification never has a write floor: the history/bundle
boundary is exactly c0 (armed rows are fully admitted), so c0 is the
only per-row scalar.

Grid = (B, kv_heads, table_entries + bundle_tiles) with the kv tile axis
innermost, so the (Cv*G, d) online-softmax state lives in VMEM scratch
across one row's tiles.  Bundle padding queries (the engine rounds Cv up
to a block multiple) produce garbage the caller discards; padding KEYS
sit at positions >= c0 + n_valid and are causally invisible to every
valid query, so no n_valid operand is needed.

``paged_verify_attention_quant`` is the int8-pool twin.  Because the
bundle spans several positions, the fp-ring recency gate is PER QUERY:
query at position qp reads history block t at full precision iff
t > qp//bs - R — the same window the int8 decode kernel would apply at
position qp — which keeps speculative attention bit-identical to the
non-speculative schedule.  The fp blocks come from a pre-round SNAPSHOT
of the row's ring tail (operand, not the pool's live ring): the engine
snapshots the ring anyway for the exact rollback restore, and the
snapshot provably covers every block any verify query gates to fp.
Since the gate differs per query row, the value accumulation selects
per (query, key) between the ring tile and the dequantized int8 tile —
two matmuls instead of one.
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


def _accumulate(ti, ntiles, s, vmm, o_ref, m_scr, l_scr, acc_scr):
    """One online-softmax step over a pre-masked score tile ``s``
    (Cv*G, bs); ``vmm(p)`` maps the softmax numerator tile to its
    (Cv*G, d) value contribution — plain ``p @ v`` for fp, a per-query
    ring/int8 select for the quant kernel.  Same recurrence as
    ``kernels.prefill_attention._accumulate`` (fp-vs-int8 lockstep), but
    the out ref carries the batched grid's (1, 1, CG, d) block."""
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
    acc_new = acc_scr[...] * alpha[:, None] + vmm(p)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ti == ntiles - 1)
    def _write():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _verify_mask(ti, c0, CG, bs, G, nbt):
    """Validity for tile ``ti`` against one row's query bundle: history
    tiles hold implicit pool positions valid strictly below c0 (armed
    rows have no write floor, so history/bundle split AT c0); bundle
    tiles hold operand positions c0 + (ti - nbt)*bs + j, causally
    visible up to each query's own position c0 + r//G."""
    j = jax.lax.broadcasted_iota(jnp.int32, (CG, bs), 1)
    qp = c0 + jax.lax.broadcasted_iota(jnp.int32, (CG, bs), 0) // G
    is_hist = ti < nbt
    kp = jnp.where(is_hist, ti * bs + j, c0 + (ti - nbt) * bs + j)
    return (kp <= qp) & split_history(is_hist, kp, c0)


def _verify_kernel(tbl_ref, c0_ref, q_ref, k_ref, v_ref, kc_ref, vc_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale, bs, nbt, G, cb):
    """One (row, kv_head, tile) program: history tiles were resolved by
    the BlockSpec index map through row b's table entry ti; bundle tiles
    to the matching slice of the bundle's fp K/V operands."""
    b, ti = pl.program_id(0), pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)               # (Cv*G, d)
    is_hist = ti < nbt
    k = jnp.where(is_hist, k_ref[0, 0], kc_ref[0, 0, 0]).astype(jnp.float32)
    v = jnp.where(is_hist, v_ref[0, 0], vc_ref[0, 0, 0]).astype(jnp.float32)
    s = q @ k.T * scale                               # (Cv*G, bs)
    s = jnp.where(_verify_mask(ti, c0_ref[b], q.shape[0], bs, G, nbt),
                  s, NEG_INF)
    _accumulate(ti, nbt + cb, s, lambda p: p @ v, o_ref, m_scr, l_scr,
                acc_scr)


def _verify_layouts(q, k_chunk, v_chunk, bs):
    """(B, Cv, H|Hkv, D) -> kernel layouts: q (B, Hkv, Cv*G, D) with
    query row r = (token r // G, group r % G); bundle K/V
    (B, Hkv, Cv/bs, bs, D)."""
    B, Cv, H, D = q.shape
    Hkv = k_chunk.shape[2]
    G = H // Hkv
    qr = (q.reshape(B, Cv, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, Cv * G, D))
    kcr = (k_chunk.reshape(B, Cv // bs, bs, Hkv, D).transpose(0, 3, 1, 2, 4))
    vcr = (v_chunk.reshape(B, Cv // bs, bs, Hkv, D).transpose(0, 3, 1, 2, 4))
    return qr, kcr, vcr


def paged_verify_attention(q, k_chunk, v_chunk, k_pool, v_pool,
                           block_tables, c0s, *, scale=None, interpret=True):
    """Batched speculative-verify attention through per-row block tables.

    q / k_chunk / v_chunk (B, Cv, H|Hkv, D): every row's draft bundle's
    roped projections at absolute positions [c0s[b], c0s[b] + Cv); pools
    (NB, bs, Hkv, D) shared by all rows; block_tables (B, NBt) int32 and
    c0s (B,) int32 are scalar-prefetched.  History (< c0) reads through
    the table; the bundle itself (>= c0) from the fp operands — sealing
    happens after attention, per layer, like chunked prefill.  Inactive
    rows carry sentinel tables and c0 = 0 and produce garbage the engine
    discards.  Returns (B, Cv, H, D)."""
    B, Cv, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = block_tables.shape[1]
    CB = Cv // bs
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr, kcr, vcr = _verify_layouts(q, k_chunk, v_chunk, bs)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D)
    vr = v_pool.transpose(2, 0, 1, 3)

    def q_ix(b, h, ti, tbl, c0):
        return (b, h, 0, 0)

    def hist_ix(b, h, ti, tbl, c0, n=NBt):
        return (h, tbl[b, jnp.minimum(ti, n - 1)], 0, 0)

    def chunk_ix(b, h, ti, tbl, c0, n=NBt, c=CB):
        return (b, h, jnp.clip(ti - n, 0, c - 1), 0, 0)

    kernel = functools.partial(_verify_kernel, scale=scale, bs=bs, nbt=NBt,
                               G=G, cb=CB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block tables + per-row c0
        grid=(B, Hkv, NBt + CB),
        in_specs=[
            pl.BlockSpec((1, 1, Cv * G, D), q_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, 1, bs, D), chunk_ix),
            pl.BlockSpec((1, 1, 1, bs, D), chunk_ix),
        ],
        out_specs=pl.BlockSpec((1, 1, Cv * G, D), q_ix),
        scratch_shapes=[
            pltpu.VMEM((Cv * G,), jnp.float32),
            pltpu.VMEM((Cv * G,), jnp.float32),
            pltpu.VMEM((Cv * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Cv * G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), c0s.astype(jnp.int32), qr, kr, vr,
      kcr, vcr)
    return (out.reshape(B, Hkv, Cv, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, Cv, H, D))


def _verify_kernel_quant(tbl_ref, c0_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, kt_ref, vt_ref, kc_ref, vc_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale, bs, nbt, G, cb,
                         rtail):
    """int8 variant: the recency gate is PER QUERY ROW — query at
    position qp reads history block ti at fp iff ti > qp//bs - rtail,
    matching what the int8 decode kernel would have done token by token.
    fp history comes from the pre-round ring SNAPSHOT operand (slot
    ti % rtail), not the pool's draft-polluted live ring.  Scores and
    values are computed on both views and selected per (query, key);
    bundle tiles collapse to the fp operands on both views, so the
    select is a no-op there."""
    b, h, ti = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)               # (Cv*G, d)
    k8 = k_ref[0, 0].astype(jnp.float32)              # (bs, d) int8 tile
    v8 = v_ref[0, 0].astype(jnp.float32)
    ks = scale_column(ks_ref, h, bs)                  # (bs, 1) f32 scales
    vs = scale_column(vs_ref, h, bs)
    kt = kt_ref[0, 0, 0].astype(jnp.float32)          # (bs, d) ring snapshot
    vt = vt_ref[0, 0, 0].astype(jnp.float32)
    kc = kc_ref[0, 0, 0].astype(jnp.float32)          # (bs, d) bundle tile
    vc = vc_ref[0, 0, 0].astype(jnp.float32)

    is_hist = ti < nbt
    k_int = jnp.where(is_hist, k8 * ks, kc)           # int8 view of tile
    v_int = jnp.where(is_hist, v8 * vs, vc)
    k_fp = jnp.where(is_hist, kt, kc)                 # fp-ring view
    v_fp = jnp.where(is_hist, vt, vc)

    CG = q.shape[0]
    c0 = c0_ref[b]
    qp = c0 + jax.lax.broadcasted_iota(jnp.int32, (CG, 1), 0) // G
    gate = is_hist & (ti > qp // bs - rtail)          # (CG, 1) per query
    gf = gate.astype(jnp.float32)

    s = jnp.where(gate, q @ k_fp.T * scale, q @ k_int.T * scale)
    s = jnp.where(_verify_mask(ti, c0, CG, bs, G, nbt), s, NEG_INF)
    _accumulate(ti, nbt + cb, s,
                lambda p: (p * gf) @ v_fp + (p * (1.0 - gf)) @ v_int,
                o_ref, m_scr, l_scr, acc_scr)


def paged_verify_attention_quant(q, k_chunk, v_chunk, k_pool, v_pool,
                                 k_scale, v_scale, k_tails, v_tails,
                                 block_tables, c0s, *, scale=None,
                                 interpret=True):
    """Fused-dequant batched verify: q / bundle K/V (B, Cv, H|Hkv, D);
    int8 pools (NB, bs, Hkv, D) with f32 scales (NB, bs, Hkv);
    k_tails/v_tails (B, R*bs, Hkv, D) — every row's PRE-ROUND fp ring
    snapshot (taken for the exact rollback restore; drafts read it too);
    block_tables (B, NBt), c0s (B,).  The table gather matches the fp
    kernel; only the per-query recency select differs.  Returns
    (B, Cv, H, D)."""
    B, Cv, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = block_tables.shape[1]
    CB = Cv // bs
    R = k_tails.shape[1] // bs
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr, kcr, vcr = _verify_layouts(q, k_chunk, v_chunk, bs)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D) int8
    vr = v_pool.transpose(2, 0, 1, 3)
    ksr = scale_layout(k_scale)                       # (NB, Hkv, bs) f32
    vsr = scale_layout(v_scale)
    ktr = (k_tails.reshape(B, R, bs, Hkv, D)          # (B, Hkv, R, bs, D)
           .transpose(0, 3, 1, 2, 4))
    vtr = (v_tails.reshape(B, R, bs, Hkv, D)
           .transpose(0, 3, 1, 2, 4))

    def q_ix(b, h, ti, tbl, c0):
        return (b, h, 0, 0)

    def hist_ix(b, h, ti, tbl, c0, n=NBt):
        return (h, tbl[b, jnp.minimum(ti, n - 1)], 0, 0)

    def hist_ix_s(b, h, ti, tbl, c0, n=NBt):
        return (tbl[b, jnp.minimum(ti, n - 1)], 0, 0)

    def ring_ix(b, h, ti, tbl, c0, r=R):
        return (b, h, ti % r, 0, 0)

    def chunk_ix(b, h, ti, tbl, c0, n=NBt, c=CB):
        return (b, h, jnp.clip(ti - n, 0, c - 1), 0, 0)

    kernel = functools.partial(_verify_kernel_quant, scale=scale, bs=bs,
                               nbt=NBt, G=G, cb=CB, rtail=R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block tables + per-row c0
        grid=(B, Hkv, NBt + CB),
        in_specs=[
            pl.BlockSpec((1, 1, Cv * G, D), q_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, 1, bs, D), hist_ix),
            pl.BlockSpec((1, Hkv, bs), hist_ix_s),
            pl.BlockSpec((1, Hkv, bs), hist_ix_s),
            pl.BlockSpec((1, 1, 1, bs, D), ring_ix),
            pl.BlockSpec((1, 1, 1, bs, D), ring_ix),
            pl.BlockSpec((1, 1, 1, bs, D), chunk_ix),
            pl.BlockSpec((1, 1, 1, bs, D), chunk_ix),
        ],
        out_specs=pl.BlockSpec((1, 1, Cv * G, D), q_ix),
        scratch_shapes=[
            pltpu.VMEM((Cv * G,), jnp.float32),
            pltpu.VMEM((Cv * G,), jnp.float32),
            pltpu.VMEM((Cv * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Cv * G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), c0s.astype(jnp.int32), qr, kr, vr,
      ksr, vsr, ktr, vtr, kcr, vcr)
    return (out.reshape(B, Hkv, Cv, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, Cv, H, D))
