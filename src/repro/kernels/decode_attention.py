"""Single-token decode attention over a (possibly ring) KV cache.

Grid = (batch * kv_heads, cache_blocks); the cache dimension is sequential
so the online-softmax state for the G grouped query heads stays in VMEM.
Validity comes from ``slot_pos`` (absolute position per slot, -1 = empty) —
the same structure the recycler trims, so a recycled + trimmed cache is
attended correctly with zero layout changes.  Ring-buffer (sliding-window)
caches work unchanged: masking is position-based, not index-based.

``paged_decode_attention`` is the block-table variant: K/V live in ONE
shared pool of fixed-size blocks and each batch row names its blocks via
a table.  The table is a *scalar-prefetch* operand, so the BlockSpec
index map gathers each row's next pool blocks by table lookup — the
kernel body never sees the indirection, and shared prefix blocks are read
in place with no per-request copy.  Its grid is (row, group of table
entries): a program reads whole pool pages over all KV heads, and the
index maps stop at the row's last valid page, so a row costs its context,
not its table width.  Validity is implicit (tile i, slot j -> position
i*block_size + j, valid iff <= the row's decode position), so no
slot_pos array exists for paged caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def check_shard_view(H: int, Hkv: int) -> None:
    """Guard the q-heads / kv-heads pairing at kernel entry.

    The paged kernels derive every head count from operand shapes, so
    under a tensor-parallel ``shard_map`` they transparently run on the
    shard-LOCAL view (H/tp query heads against an Hkv/tp pool) — both
    operands must come from the SAME shard.  Mixing views (a head-sharded
    q against an unsharded pool, or vice versa) breaks the grouped
    reshape; for MHA-ratio pools that surfaces here as a non-divisible
    head pair instead of as a silently wrong grouping downstream.  A GQA
    mismatch whose wrong ratio still divides passes this check — the
    token-identity suites are the real gate."""
    if Hkv <= 0 or H % Hkv:
        raise ValueError(
            f"query heads ({H}) not a multiple of kv heads ({Hkv}); "
            "under shard_map both operands must be the same shard's "
            "local view — mixing a head-sharded tensor with an "
            "unsharded one produces exactly this mismatch")


def split_history(is_hist, kp, w):
    """Validity of key positions ``kp`` on either side of the
    history/chunk boundary ``w``: history tiles hold positions below it,
    chunk tiles positions at or above it.  Written with ``&``/``|``
    because Mosaic cannot lower a select between two boolean vectors."""
    return (is_hist & (kp < w)) | (~is_hist & (kp >= w))


def scale_layout(scale):
    """Pool scales (NB, bs, Hkv) -> kernel layout (NB, Hkv, bs).  One pool
    block's scales for every head then form a (Hkv, bs) tile whose two
    minor dims are whole array dims — a legal TPU block with ``bs`` on
    lanes.  A per-head (1, bs) block would be neither 8-aligned nor the
    whole head axis, which the TPU lowering refuses."""
    return scale.transpose(0, 2, 1)


def scale_column(ref, h, bs):
    """The (bs, 1) scale column of kv head ``h`` from a (1, Hkv, bs)
    ``scale_layout`` tile.  Both steps are masked sums in which every
    other term is exactly 0, so the values are bit-exact copies; they
    avoid a dynamic sublane slice and a lane-to-sublane transpose."""
    s = ref[0].astype(jnp.float32)                    # (Hkv, bs)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    row = jnp.sum(jnp.where(head == h, s, 0.0), axis=0, keepdims=True)
    r = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, window, bk, nk):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (G, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    sp = sp_ref[...]                                  # (bk,) abs positions
    pos = pos_ref[0]

    s = q @ k.T * scale                               # (G, bk)
    ok = (sp >= 0) & (sp <= pos)
    if window:
        ok &= sp > pos - window
    s = jnp.where(ok[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def _decode_kernel_batched(pos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, scale, window, bk, nk,
                           hkv):
    """Per-row variant: slot_pos and pos are indexed by the batch row this
    (batch*head) program belongs to, so every slot-pool row is masked by its
    own request's validity/causality — rows never see each other's slots."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (G, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    sp = sp_ref[0]                                    # (bk,) this row's slots
    pos = pos_ref[pl.program_id(0) // hkv]            # this row's position

    s = q @ k.T * scale                               # (G, bk)
    ok = (sp >= 0) & (sp <= pos)
    if window:
        ok &= sp > pos - window
    s = jnp.where(ok[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention_batched(q, k_cache, v_cache, slot_pos, pos, *, window=0,
                             block_k=256, scale=None, interpret=True):
    """Continuous-batching decode: q (B,1,H,D); caches (B,C,Hkv,D);
    slot_pos (B,C) per-row; pos (B,) per-row int32.  Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = scale or D ** -0.5
    bk = min(block_k, C)
    assert C % bk == 0, (C, bk)
    nk = C // bk

    qr = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, C, D)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, C, D)
    pos_arr = pos.astype(jnp.int32)

    kernel = functools.partial(_decode_kernel_batched, scale=scale,
                               window=window, bk=bk, nk=nk, hkv=Hkv)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk), lambda bh, ki, hkv=Hkv: (bh // hkv, ki)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qr, kr, vr, slot_pos)
    return out.reshape(B, Hkv, G, D).reshape(B, 1, H, D)


def _paged_accumulate(ti, nbt, q_ref, k, v, pos, o_ref, m_scr, l_scr,
                      acc_scr, *, scale, bs):
    """Body of the int8 paged decode kernel: one online-softmax step of
    the G grouped query heads against this program's (bs, d) K/V tile,
    masked by implicit positions (tile ti slot j == position ti*bs + j,
    valid iff <= the row's decode position), with the normalized write on
    the last tile.  The fp kernel computes the same step (f32 scores and
    sums, the same mask, scale and ``1e-30`` guard) over whole pages of
    every head."""
    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (G, d)
    tok = ti * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)

    s = q @ k.T * scale                               # (G, bs)
    s = jnp.where(tok <= pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ti == nbt - 1)
    def _write():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


# K+V bytes one grid step should move: the rule that sizes a page group
# (``_group_pages``), fixed from a sweep of 1, 2, 4 and 8 pages a step on
# a TPU v5e at the benchmark's widths (PERF.md section 6).
_STEP_BYTES = 512 << 10


def _group_pages(bs, hkv, d, itemsize, nbt):
    """Pages per grid step: the fewest whose K+V reach ``_STEP_BYTES``,
    and no more than the table holds."""
    page = 2 * bs * hkv * d * itemsize
    return max(1, min(-(-_STEP_BYTES // page), nbt))


def _lane_heads(hkv, d):
    """KV heads laid side by side in one row of a page slab: the most
    that divide Hkv and fit in 128 lanes (one, where a head fills them),
    so that D = 64 pages are read lane-dense and each head keeps to one
    lane block of every row."""
    return max(c for c in range(1, hkv + 1)
               if hkv % c == 0 and c * d <= max(128, d))


def _last_page(pos, bs, nbt):
    """The row's last valid table entry.  Idle rows keep counting ``pos``
    past the table, hence the clamp."""
    return jnp.minimum(jnp.maximum(pos, 0) // bs, nbt - 1)


def _paged_decode_kernel(tbl_ref, pos_ref, q_ref, *refs, scale, bs, nbt,
                         hkv, lanes, pages):
    """One (row, group of ``pages`` table entries) program over all the
    row's query heads.  Page j of the group arrives whole in k_refs[j] /
    v_refs[j] as its (bs * Hkv / lanes, lanes * d) pool slab; past the
    row's last valid page the index maps repeat the previous block index
    (no DMA), and the body skips the groups past it.  q_ref holds each
    query head in the lane block of its kv head
    (zeros elsewhere), so one product scores every head against every
    slab row; the output keeps that lane block."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * pages:]
    g = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    last = _last_page(pos, bs, nbt)
    lim = jnp.minimum(pos, nbt * bs - 1)          # the last valid token

    @pl.when(g == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(g * pages <= last)
    def _walk():
        # slab row r holds token r // nblk of the group for kv heads
        # (r % nblk) * lanes + [0, lanes); entries past ``last`` hold
        # stale pages and are masked with every token past ``lim``
        k = jnp.concatenate([r[0] for r in k_refs]).astype(jnp.float32)
        v = jnp.concatenate([r[0] for r in v_refs]).astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)              # (H, lanes * d)
        H = q.shape[0]
        nblk = hkv // lanes
        shape = (H, k.shape[0])
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        blk = jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
            // (H // hkv * lanes)
        ok = (blk == row % nblk) & (g * pages * bs + row // nblk <= lim)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(g == last // pages)
    def _write():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, *,
                           scale=None, interpret=True):
    """Block-table decode: q (B,1,H,D); pools (NB, bs, Hkv, D) shared by
    every request; block_tables (B, NBt) int32 (sentinel-0 padded);
    pos (B,) per-row int32.  Returns (B,1,H,D).  Every count comes from
    the operand shapes, so a tensor-parallel shard runs it on its local
    heads unchanged."""
    B, _, H, D = q.shape
    NB, bs, Hkv = k_pool.shape[:3]
    NBt = block_tables.shape[1]
    check_shard_view(H, Hkv)
    scale = scale or D ** -0.5
    pages = _group_pages(bs, Hkv, D, k_pool.dtype.itemsize, NBt)
    lanes = _lane_heads(Hkv, D)
    R, L = bs * Hkv // lanes, lanes * D               # page slab shape
    # lane block of each query head: its kv head's place in a slab row
    block = (np.arange(H) // (H // Hkv)) % lanes
    onehot = block[:, None] == np.arange(lanes)       # (H, lanes)

    def page_spec(j):
        def index(b, g, tbl, pos):
            # past the row's last page, the entry this input held a group
            # earlier: the block index repeats, so the pipeline fetches
            # nothing (a row shorter than the group repeats its last page)
            last = _last_page(pos[b], bs, NBt)
            ti = jnp.minimum(g, last // pages) * pages + j
            ti = jnp.where(ti > last, ti - pages, ti)
            return tbl[b, jnp.where(ti < 0, last, ti)], 0, 0
        return pl.BlockSpec((1, R, L), index)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               nbt=NBt, hkv=Hkv, lanes=lanes, pages=pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block table + per-row positions
        grid=(B, pl.cdiv(NBt, pages)),
        in_specs=[pl.BlockSpec((1, H, L), lambda b, g, tbl, pos: (b, 0, 0))]
        + [page_spec(j) for j in range(pages)] * 2,
        out_specs=pl.BlockSpec((1, H, L), lambda b, g, tbl, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, L), jnp.float32),
        ],
    )
    q_wide = jnp.where(onehot[:, :, None], q.reshape(B, H, 1, D), 0)
    k_slabs = k_pool.reshape(NB, R, L)
    v_slabs = v_pool.reshape(NB, R, L)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, L), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q_wide.reshape(B, H, L), *[k_slabs] * pages, *[v_slabs] * pages)
    out = out.reshape(B, H, lanes, D)[:, np.arange(H), block]
    return out.reshape(B, 1, H, D)


def _paged_decode_kernel_quant(tbl_ref, pos_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, kt_ref, vt_ref, o_ref,
                               m_scr, l_scr, acc_scr, *, scale, bs, nbt,
                               hkv, rtail):
    """One (batch*kv_head, table_entry) program of the int8 pool: K/V
    tiles arrive as int8 pool blocks plus their per-vector f32 scales
    (table-lookup index map), and the dequant multiply is fused into the
    gather, over every table entry of the row — HBM traffic
    for sealed blocks is the int8 bytes.  The row's most recent ``rtail``
    blocks are instead read from its fp ring tail (ring slot ti % rtail),
    so quantization error never sits where attention mass is largest."""
    ti = pl.program_id(1)
    h = pl.program_id(0) % hkv
    k8 = k_ref[0, 0].astype(jnp.float32)              # (bs, d) int8 tile
    v8 = v_ref[0, 0].astype(jnp.float32)
    ks = scale_column(ks_ref, h, bs)                  # (bs, 1) f32 scales
    vs = scale_column(vs_ref, h, bs)
    kt = kt_ref[0, 0].astype(jnp.float32)             # (bs, d) fp ring tile
    vt = vt_ref[0, 0].astype(jnp.float32)
    pos = pos_ref[pl.program_id(0) // hkv]            # this row's position

    open_b = pos // bs
    use_fp = (ti <= open_b) & (ti > open_b - rtail)   # scalar: recent block?
    k = jnp.where(use_fp, kt, k8 * ks)
    v = jnp.where(use_fp, vt, v8 * vs)
    _paged_accumulate(ti, nbt, q_ref, k, v, pos, o_ref, m_scr, l_scr,
                      acc_scr, scale=scale, bs=bs)


def paged_decode_attention_quant(q, k_pool, v_pool, k_scale, v_scale,
                                 k_tail, v_tail, block_tables, pos, *,
                                 scale=None, interpret=True):
    """Fused-dequant block-table decode: q (B,1,H,D); int8 pools
    (NB, bs, Hkv, D) with f32 scales (NB, bs, Hkv); per-row fp ring tails
    (B, R*bs, Hkv, D); block_tables (B, NBt) int32; pos (B,).  The
    scalar-prefetch table gather is unchanged from the fp kernel — only
    the tile contents differ (int8 + scale, or the fp ring slot for the
    row's most recent R blocks).  Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    NBt = block_tables.shape[1]
    R = k_tail.shape[1] // bs
    check_shard_view(H, Hkv)
    G = H // Hkv
    scale = scale or D ** -0.5

    qr = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kr = k_pool.transpose(2, 0, 1, 3)                 # (Hkv, NB, bs, D) int8
    vr = v_pool.transpose(2, 0, 1, 3)
    ksr = scale_layout(k_scale)                       # (NB, Hkv, bs) f32
    vsr = scale_layout(v_scale)
    ktr = (k_tail.reshape(B, R, bs, Hkv, D)           # (B*Hkv, R, bs, D)
           .transpose(0, 3, 1, 2, 4).reshape(B * Hkv, R, bs, D))
    vtr = (v_tail.reshape(B, R, bs, Hkv, D)
           .transpose(0, 3, 1, 2, 4).reshape(B * Hkv, R, bs, D))

    kernel = functools.partial(_paged_decode_kernel_quant, scale=scale,
                               bs=bs, nbt=NBt, hkv=Hkv, rtail=R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block table + per-row positions
        grid=(B * Hkv, NBt),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, ti, tbl, pos: (bh, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda bh, ti, tbl, pos, hkv=Hkv:
                         (bh % hkv, tbl[bh // hkv, ti], 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda bh, ti, tbl, pos, hkv=Hkv:
                         (bh % hkv, tbl[bh // hkv, ti], 0, 0)),
            pl.BlockSpec((1, Hkv, bs),
                         lambda bh, ti, tbl, pos, hkv=Hkv:
                         (tbl[bh // hkv, ti], 0, 0)),
            pl.BlockSpec((1, Hkv, bs),
                         lambda bh, ti, tbl, pos, hkv=Hkv:
                         (tbl[bh // hkv, ti], 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda bh, ti, tbl, pos, r=R: (bh, ti % r, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda bh, ti, tbl, pos, r=R: (bh, ti % r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bh, ti, tbl, pos: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      qr, kr, vr, ksr, vsr, ktr, vtr)
    return out.reshape(B, Hkv, G, D).reshape(B, 1, H, D)


def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window=0,
                     block_k=256, scale=None, interpret=True):
    """q: (B,1,H,D); caches (B,C,Hkv,D); slot_pos (C,); pos scalar int32.
    Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = scale or D ** -0.5
    bk = min(block_k, C)
    assert C % bk == 0, (C, bk)
    nk = C // bk

    qr = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, C, D)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, C, D)
    pos_arr = jnp.reshape(pos, (1,)).astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((bk,), lambda bh, ki: (ki,)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qr, kr, vr, slot_pos)
    return out.reshape(B, Hkv, G, D).reshape(B, 1, H, D)
