"""Paged continuous-batching engine: block-table KV pool with ref-counted
prefix sharing (the device-resident recycling tier).

The dense slot pool (``BatchedEngine``) gives every in-flight request a
private ``[capacity]`` KV row and materializes every recycled hit from the
host store — two requests sharing a 500-token prefix hold two device
copies and both pay a host→device transfer.  This engine replaces the row
with a *block table*: all K/V lives in ONE shared pool of ``block_size``-
token blocks (``models.cache.init_paged_pool``), request r's cache is the
ordered list of pool block ids in its table, and a block may appear in any
number of tables at once.

Two cache tiers serve admissions:

  L1 — ``core.radix.BlockTrie``: token-block keys → live device blocks.
       A warm-prefix admission composes its table from the resident chain
       with **zero host round-trip**: shared full blocks are referenced in
       place (refcount++), and only the divergent boundary block is
       materialized fresh (copy-on-write by recomputation — a shared
       block is never written in place, and the donor is never even
       read).
  L2 — the existing ``Recycler``/``HostKVStore`` path: on an L1 miss the
       host entry is promoted back to device in block-granular chunks and
       indexed in L1 for the next admission.

Admission itself is **paged-native chunked prefill** (PR 5, the
default): the prompt's fresh region is processed as a sequence of
fixed-size, block-aligned chunks, each writing K/V straight into freshly
allocated pool blocks and attending history through the block table
(``kernels.paged_prefill_attention``) — no staging cache, no
gather/scatter round-trip — and ``decode_batch`` advances ONE chunk per
pending admission per engine step, interleaved with the batched decode,
so long prompts never stall the in-flight batch.  The original staged
path (full-capacity staging cache + dense prefill + scatter) survives
behind ``prefill_mode="staged"`` as the reference baseline.

Static shapes still rule: the pool is one fixed ``[num_blocks, bs, ...]``
allocation per layer, tables are fixed-width (sentinel-0 padded), ONE
compiled decode executable (`decode_step` over the paged cache) advances
every in-flight request per step regardless of occupancy or sharing, and
one compiled chunk-prefill executable per fixed chunk shape serves every
admission regardless of suffix length (the staged path compiled one per
DISTINCT length).

``kv_quant=True`` stores the L1 pool in **int8** (``repro.core.quant``
scheme, shared with the host tier): ~2-4x more resident blocks per HBM
byte, dequant fused into the block-table gather
(``kernels.paged_decode_attention_quant``), a per-row fp ring tail over
the most recent blocks, and int8-verbatim block movement between the
tiers — see the ``PagedEngine`` docstring for the one-quantization
invariant.

Correctness contract (tests/test_paged_pool.py,
tests/test_chunked_prefill.py): paged decode is token-for-token identical
to the dense slot pool — and therefore to serial ``generate`` — for every
admission mode (and the int8 pool to the fp pool, and the chunked
admission route to the staged one); blocks shared between requests have
refcount > 1 and are never written by either sharer, including by any
chunk step of a sharer's admission.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import BlockAllocator, BlockPoolExhausted, BlockTrie
from repro.core.blockpool import (SENTINEL, AdmissionRejected,
                                  PoolSaturated)
from repro.core.faults import InjectedFault
from repro.core.kvstore import to_host, tree_bytes
from repro.core import quant as kvq
from repro.core.quant import dequantize_vectors_jnp, quantize_vectors_jnp
from repro.core.recycler import GraftPlan, grow_capacity
from repro.data.tokenizer import EOS
from repro.models import (decode_step, draft_refine, draft_view,
                          init_cache, init_paged_pool, paged_block_bytes,
                          prefill_paged, prefill_paged_packed, verify_paged)
from repro.runtime import Runtime, on_tpu
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine, GenResult, _Slot
from repro.serving.sampling import sample_batched, sample_logits
from repro.serving.trace import span


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pack_admission_segments(segs, *, block_size: int, buckets,
                            max_segments: int, table_width: int):
    """Build the ragged packed-prefill descriptor batch from per-admission
    chunk segments — the pure (numpy in, numpy out) heart of
    ``prefill_mode="packed"``, property-tested standalone.

    ``segs`` is a list of per-admission tuples ``(row, table_row, c0,
    w_floor, n_valid, C, tokens)``: pool row, host table mirror (NBt,),
    block-aligned chunk start, write floor, valid token count, chunk
    width (a multiple of ``block_size``), and the ``n_valid`` prompt ids.
    Segments are laid out back to back in the packed token buffer, each
    occupying exactly ``C`` positions (tokens past ``n_valid`` are
    padding), and the buffer is padded up to the smallest bucket in
    ``buckets`` that fits — so the dispatch shape depends only on the
    bucket ladder, never on the segment count or any suffix length.

    Descriptor arrays are fixed at ``max_segments + 1`` entries: index
    ``len(segs)`` is the dedicated PADDING segment (row 0, all-sentinel
    table, c0 = w_floor = valid = 0) whose ``q_off`` is the pack end, so
    pad tokens get small in-range positions, attend only the pad region,
    and write only the sentinel scratch block.

    Returns ``{"tokens" (1, T), "rows", "tables", "c0s", "w_floors",
    "valids", "q_offs" (all (S,)), "seg_ids" (T,)}`` with S =
    max_segments + 1."""
    assert 0 < len(segs) <= max_segments, (len(segs), max_segments)
    S = max_segments + 1
    total = sum(C for (_, _, _, _, _, C, _) in segs)
    T = next((b for b in sorted(buckets) if b >= total), None)
    if T is None:
        raise ValueError(f"packed total {total} exceeds largest bucket "
                         f"{max(buckets)}")
    tokens = np.zeros((1, T), np.int32)
    rows = np.zeros((S,), np.int32)
    tables = np.full((S, table_width), SENTINEL, np.int32)
    c0s = np.zeros((S,), np.int32)
    w_floors = np.zeros((S,), np.int32)
    valids = np.zeros((S,), np.int32)
    # unused / padding segments: q_off at the pack end so every pad
    # token's position (t - q_off) stays small and in range
    q_offs = np.full((S,), total, np.int32)
    seg_ids = np.full((T,), len(segs), np.int32)
    off = 0
    for i, (row, table_row, c0, w_floor, n_valid, C, toks) in \
            enumerate(segs):
        assert C % block_size == 0 and 0 < n_valid <= C, (C, n_valid)
        assert c0 % block_size == 0, c0
        rows[i] = row
        tables[i] = table_row
        c0s[i] = c0
        w_floors[i] = w_floor
        valids[i] = n_valid
        q_offs[i] = off
        seg_ids[off:off + C] = i
        tokens[0, off:off + n_valid] = toks
        off += C
    return {"tokens": tokens, "rows": rows, "tables": tables, "c0s": c0s,
            "w_floors": w_floors, "valids": valids, "q_offs": q_offs,
            "seg_ids": seg_ids}


# ---------------------------------------------------------------------------
# jitted pool <-> staging composition (device-to-device; no host traffic)
# ---------------------------------------------------------------------------
def _stage_from_pool(pool, chain_ids, depth: int, cap: int):
    """Compose a dense single-request staging cache holding positions
    [0, depth) gathered from pool blocks ``chain_ids`` — the layout the
    existing (compiled) prefill consumes.  Pure device gather.  Staging
    is always full precision: int8 pools dequantize in the gather (the
    prefill needs fp operands anyway; the int8 bytes in the pool are
    untouched)."""
    stage = {}
    for seg, c in pool.items():
        sub = {}
        for name in ("k", "v"):
            a = c[name][:, chain_ids]              # (L, ncb, bs, H, D)
            if name + "_scale" in c:
                a = dequantize_vectors_jnp(
                    a, c[name + "_scale"][:, chain_ids], c["k_tail"].dtype)
            L = a.shape[0]
            a = a.reshape(L, -1, *a.shape[3:])[:, :depth]
            a = jnp.pad(a, ((0, 0), (0, cap - depth), (0, 0), (0, 0)))
            sub[name] = a[:, None]                 # (L, 1, cap, H, D)
        pos = jnp.arange(cap, dtype=jnp.int32)
        sp = jnp.where(pos < depth, pos, -1)
        sub["slot_pos"] = jnp.broadcast_to(sp, (c["k"].shape[0], cap))
        stage[seg] = sub
    return stage


def _gather_quant(pool, chain_ids, depth: int, cap: int):
    """Harvest gather for int8 pools: like ``_stage_from_pool`` but the
    int8 codes and f32 scales are copied VERBATIM (no dequant) — the host
    entry built from this keeps the pool's exact bits, so a later
    promotion can put them back without a requant round-trip."""
    stage = {}
    for seg, c in pool.items():
        sub = {}
        for name in ("k", "v", "k_scale", "v_scale"):
            a = c[name][:, chain_ids]              # (L, ncb, bs, H[, D])
            L = a.shape[0]
            a = a.reshape(L, -1, *a.shape[3:])[:, :depth]
            pad = [(0, 0), (0, cap - depth)] + [(0, 0)] * (a.ndim - 2)
            sub[name] = jnp.pad(a, pad)[:, None]   # (L, 1, cap, H[, D])
        pos = jnp.arange(cap, dtype=jnp.int32)
        sp = jnp.where(pos < depth, pos, -1)
        sub["slot_pos"] = jnp.broadcast_to(sp, (c["k"].shape[0], cap))
        stage[seg] = sub
    return stage


def _scatter_to_pool(pool, stage, dst_ids, start: int, n: int, bs: int):
    """Write staging positions [start, start + n) into pool blocks
    ``dst_ids`` (dst_ids[i] holds positions [start + i*bs, ...)).  The
    copy-on-write boundary block is materialized here: staging already
    holds the donor prefix for [start, depth), so the divergent block's
    private copy costs no extra pass.  int8 pools quantize the scattered
    region here — for fresh tokens this is their first (and only)
    quantization."""
    ps = start + jnp.arange(n, dtype=jnp.int32)
    blk = dst_ids[(ps - start) // bs]
    off = ps % bs
    out = {}
    for seg, c in pool.items():
        upd = {}
        for name in ("k", "v"):
            vals = stage[seg][name][:, 0, start:start + n]
            if name + "_scale" in c:
                q, s = quantize_vectors_jnp(vals)
                upd[name] = c[name].at[:, blk, off].set(q)
                upd[name + "_scale"] = \
                    c[name + "_scale"].at[:, blk, off].set(s)
            else:
                upd[name] = c[name].at[:, blk, off].set(vals)
        out[seg] = {**c, **upd}
    return out


def _upload_q8(pool, ent, dst_ids, bs: int):
    """L2 -> L1 promotion of a quantized host entry's int8 region: copy
    the stored int8 codes + f32 scales straight into pool blocks
    ``dst_ids`` (positions [0, n), block-aligned).  No dequant/requant —
    the bits that were quantized once at the vectors' first write are the
    bits that land back in the pool."""
    out = {}
    for seg, c in pool.items():
        e = ent[seg]
        n = e["k"].shape[2]
        ps = jnp.arange(n, dtype=jnp.int32)
        blk = dst_ids[ps // bs]
        off = ps % bs
        upd = {name: c[name].at[:, blk, off].set(e[name][:, 0])
               for name in e}
        out[seg] = {**c, **upd}
    return out


def _fill_tail(pool, stage, row, m):
    """Populate pool row ``row``'s fp ring tail from a staging cache:
    ring slot r receives the fp values of block ``ti(r)`` — the unique
    block in the row's initial recency window (m//bs - R, m//bs] with
    ti % R == r — so the first decode step already attends its most
    recent R blocks at full precision.  Slots whose ti falls before the
    prompt (or holds no data yet) are zeroed; the kernel's recency gate
    never selects them.  int8 staging is dequantized here — tail fidelity
    is best-effort for admitted positions (exact for fp misses' freshly
    prefilled tokens, dequant for promoted/resident ones) and exact for
    every token decode later dual-writes."""
    out = {}
    for seg, c in pool.items():
        bs = c["k"].shape[2]                       # (L, NB, bs, H, D)
        R = c["k_tail"].shape[2] // bs             # (L, B, R*bs, H, D)
        cap = stage[seg]["k"].shape[2]             # (L, 1, cap, H[, D])
        open_b = m // bs
        r = jnp.arange(R, dtype=jnp.int32)
        ti = open_b - ((open_b - r) % R)           # block held by ring slot r
        j = jnp.arange(bs, dtype=jnp.int32)
        posm = ti[:, None] * bs + j[None]          # (R, bs) abs positions
        valid = ((posm >= 0) & (posm < cap)).reshape(-1)
        idx = jnp.clip(posm, 0, cap - 1).reshape(-1)
        upd = {}
        for name in ("k", "v"):
            vals = stage[seg][name][:, 0, idx]
            if name + "_scale" in stage[seg]:
                vals = dequantize_vectors_jnp(
                    vals, stage[seg][name + "_scale"][:, 0, idx],
                    c[name + "_tail"].dtype)
            vals = vals * valid[None, :, None, None]
            upd[name + "_tail"] = c[name + "_tail"].at[:, row].set(vals)
        out[seg] = {**c, **upd}
    return out


def _set_table_entries(pool, rows, idxs, blks):
    """Batched block-table update: entry (rows[j], idxs[j]) <- blks[j] in
    every layer, ONE dispatch for however many rows crossed a block
    boundary (or had one speculatively reserved) this step.  Padding
    entries carry idx == table width: out of bounds, dropped — so one
    fixed-width executable serves every update count."""
    out = {}
    for seg, c in pool.items():
        out[seg] = {**c, "block_tables":
                    c["block_tables"].at[:, rows, idxs].set(blks,
                                                            mode="drop")}
    return out


def _upload_fp_block(pool, blkdata, dst):
    """L2 -> L1 promotion of ONE block of a full-precision host entry:
    ``blkdata[seg]`` holds (L, bs, H, D) fp K/V for the block's positions.
    int8 pools quantize here — for entries that carried no sealed codes
    (fp entries, residual tails, converted legacy layouts) this is the
    vectors' first and only quantization.  Fixed shapes: one compiled
    executable regardless of how many blocks a promotion moves."""
    out = {}
    for seg, c in pool.items():
        upd = {}
        for name in ("k", "v"):
            vals = blkdata[seg][name]
            if name + "_scale" in c:
                q, s = quantize_vectors_jnp(vals)
                upd[name] = c[name].at[:, dst].set(q)
                upd[name + "_scale"] = c[name + "_scale"].at[:, dst].set(s)
            else:
                upd[name] = c[name].at[:, dst].set(vals)
        out[seg] = {**c, **upd}
    return out


def _upload_q8_block(pool, entblk, dst):
    """Verbatim int8 promotion of ONE sealed host-entry block: codes +
    scales land bit-exactly in pool block ``dst`` (the one-quantization
    invariant), with the same fixed per-block shape as the fp upload."""
    out = {}
    for seg, c in pool.items():
        upd = {name: c[name].at[:, dst].set(entblk[seg][name])
               for name in entblk[seg]}
        out[seg] = {**c, **upd}
    return out


def _set_row_tail(pool, row, tails):
    """Install a precomputed fp ring tail for row ``row`` (host-promotion
    seeding: the entry's fp residual provides exact values for the blocks
    preceding the first chunk)."""
    out = {}
    for seg, c in pool.items():
        upd = {n + "_tail": c[n + "_tail"].at[:, row].set(tails[seg][n])
               for n in ("k", "v")}
        out[seg] = {**c, **upd}
    return out


def _seed_tail_from_pool(pool, row, table_row, aligned):
    """Seed row ``row``'s fp ring tail for a RESIDENT-prefix chunked
    admission: ring slot r receives the dequantized pool content of the
    unique block ti in the window (aligned/bs - R, aligned/bs) with
    ti % R == r, so the first chunk's queries read their recent history at
    ring (not int8) fidelity — the same dequant values the staged path's
    staging gather would have produced.  Slots whose ti falls before the
    prompt are zeroed; the recency gates never select them.  The table is
    an explicit operand: the row's device table is still all-sentinel
    mid-admission."""
    out = {}
    for seg, c in pool.items():
        bs = c["k"].shape[2]                   # (L, NB, bs, H, D)
        R = c["k_tail"].shape[2] // bs
        ab = aligned // bs
        r = jnp.arange(R, dtype=jnp.int32)
        ti = (ab - 1) - ((ab - 1 - r) % R)     # block held by ring slot r
        valid = ti >= 0
        tbl = table_row
        blk = jnp.where(valid, tbl[jnp.clip(ti, 0, tbl.shape[0] - 1)], 0)
        upd = {}
        for name in ("k", "v"):
            a = c[name][:, blk]                # (L, R, bs, H, D)
            if name + "_scale" in c:
                a = dequantize_vectors_jnp(a, c[name + "_scale"][:, blk],
                                           c[name + "_tail"].dtype)
            a = a * valid[None, :, None, None, None]
            upd[name + "_tail"] = c[name + "_tail"].at[:, row].set(
                a.reshape(a.shape[0], -1, *a.shape[3:]))
        out[seg] = {**c, **upd}
    return out


def _ring_restore(pool, snap, ps, fbs):
    """Exact fp-ring rollback after a speculative round (int8 pools).

    Element (r, o) of row b's ring should end the round holding position
    q_correct = ti(r) * bs + o with ti(r) = fb - ((fb - r) % R) — the
    newest block <= the accept frontier ``fbs[b]`` congruent to r mod R,
    exactly what token-by-token decoding through the frontier would have
    left there.  Elements with q_correct >= ``ps[b]`` (the round's first
    written position) were rewritten by the verify pass with their exact
    values and are KEPT; every other element either was never touched
    (the snapshot equals the live ring) or was clobbered by a rejected
    write that wrapped onto an older slot, and is restored from the
    pre-round snapshot — which is exact there, because a slot's correct
    holder only changes when its position enters [ps, fb*bs + bs), i.e.
    when q_correct >= ps.  Rows not in the round pass ps = -2**30 (keep
    everything; their rings were only scribbled at stale positions the
    recency gates never select, same as every plain decode step)."""
    out = {}
    for seg, c in pool.items():
        bs = c["k"].shape[2]                   # (L, NB, bs, H, D)
        n = c["k_tail"].shape[2]               # (L, B, R*bs, H, D)
        R = n // bs
        idx = jnp.arange(n, dtype=jnp.int32)
        r, o = idx // bs, idx % bs
        ti = fbs[:, None] - ((fbs[:, None] - r[None]) % R)
        qc = ti * bs + o[None]                 # (B, R*bs)
        keep = (qc >= ps[:, None])[None, :, :, None, None]
        out[seg] = {**c,
                    "k_tail": jnp.where(keep, c["k_tail"],
                                        snap[seg]["k_tail"]),
                    "v_tail": jnp.where(keep, c["v_tail"],
                                        snap[seg]["v_tail"])}
    return out


def _set_row(pool, tokens, pos, row, table_row, tok0, m):
    out = {}
    for seg, c in pool.items():
        out[seg] = {**c,
                    "block_tables": c["block_tables"].at[:, row].set(table_row)}
    return out, tokens.at[row].set(tok0), pos.at[row].set(m)


def _clear_row(pool, row):
    out = {}
    for seg, c in pool.items():
        out[seg] = {**c, "block_tables":
                    c["block_tables"].at[:, row].set(SENTINEL)}
    return out


@dataclass
class _PendingAdmission:
    """A chunked admission in flight: the row is occupied but not yet
    decoding.  One chunk step runs per engine step, interleaved with the
    batched decode dispatch, so a long prompt never stalls the batch.
    The tier lookup is deferred to the FIRST chunk step (``started``),
    which lets an admission share blocks that a neighbor admitted in the
    same scheduler step has already sealed and registered."""
    st: _Slot
    next_c0: int = 0              # next chunk's (block-aligned) start
    w_floor: int = 0              # first pool position the chunks may write
    started: bool = False         # tier lookup + prefix setup done?
    # semantic block-donor graft state (None/-1 when no graft in flight).
    # ``segs`` splits the prompt into recompute segments around the
    # grafted interior: [(0, seg1_end), (graft_end, m)] — the chunks skip
    # [seg1_end, graft_end) entirely.  ``gate_at`` is the position where
    # the fidelity gate runs (end of segment 1); ``reg_cap`` caps the L1
    # trie registration frontier so approximate (grafted / post-graft)
    # blocks are NEVER indexed under the new prompt's token keys.
    segs: Optional[List[Tuple[int, int]]] = None
    seg_i: int = 0
    gate_at: int = -1
    reg_cap: int = 1 << 30
    graft: Optional[GraftPlan] = None
    graft_ref: Optional[dict] = None  # donor fp boundary K/V (gate ref)


class PagedEngine(Engine):
    """Continuous batching over a paged, prefix-shared device KV pool.

    Drop-in replacement for ``BatchedEngine`` behind the scheduler surface
    (``free_slots`` / ``admit_slot`` / ``decode_batch``); the dense slot
    pool stays as the equivalence reference.  Trunk attention only, no
    sliding window (paged blocks have no ring semantics).

    ``kv_quant=True`` switches the pool to the **int8 tier layout**
    (``core.quant`` scheme): pool K/V are int8 with per-vector f32 scales
    — ~2-4x more resident blocks per HBM byte, i.e. deeper batches and
    longer shareable prefixes on the same hardware.  Decode fuses the
    dequant into the block-table gather, and each row's most recent
    ``fp_tail_blocks`` blocks are attended from a full-precision ring
    tail (the device analogue of the host residual tail, which keeps
    greedy decoding token-identical to the fp pool).  The host (L2) tier
    holds quantized-tree entries with an fp residual tail; both tiers
    share one scheme, so:

      * a token's K/V is quantized ONCE — at the scatter/seal of its
        block (prefill scatter or decode dual-write);
      * harvest copies pool int8 verbatim into the host entry, and
        promotion copies the entry's full int8 blocks verbatim back into
        pool blocks — no dequant/requant round-trip (only the sub-block
        remainder of a partial boundary block re-quantizes, a
        value-preserving <= half-step event bounded to < block_size
        tokens per promotion);
      * the entry's fp residual tail feeds the pool's fp ring tail — the
        recent window is exact for entries admitted from an fp staging
        cache (precache, instant finish) and dequant-precision for
        pool-harvested ones, whose tails are rebuilt from the sealed int8
        codes (see ROADMAP "Int8 two-tier quantization", Known limits).
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 capacity: int = 256, num_blocks: Optional[int] = None,
                 fp_tail_blocks: int = 2, prefill_mode: str = "chunked",
                 prefill_chunk: Optional[int] = None,
                 prealloc_watermark: int = 1,
                 graft_max_div: float = 0.35,
                 speculative: bool = False, gamma: int = 4,
                 sink_blocks: int = 1, recent_blocks: int = 3,
                 spec_iters: int = 2,
                 preempt_policy: str = "least_progress",
                 overcommit: bool = False,
                 fault_plan=None, **kw):
        # the paged kernels on a TPU, the jnp references elsewhere
        kw.setdefault("rt", Runtime(use_pallas=on_tpu()))
        if kw.get("kv_quant"):
            # the int8 tier compresses its host tier by default, with a
            # residual deep enough that a promoted prefix can fill the
            # whole device fp ring tail with exact values
            kw.setdefault("compress_host_cache", True)
            kw.setdefault("compress_residual",
                          (fp_tail_blocks + 1) * kw.get("block_size", 64))
        super().__init__(cfg, params, **kw)
        if self.window:
            raise NotImplementedError("paged pool does not support "
                                      "sliding-window rings")
        bs = self.block                      # page size == radix block size
        if capacity % bs:
            capacity = _ceil_div(capacity, bs) * bs
        self.max_batch = max_batch
        self.capacity = capacity
        self.fp_tail_blocks = fp_tail_blocks
        self.nbt = capacity // bs            # fixed table width
        if num_blocks is None:
            # worst case every row full + one row's worth of retained
            # prefixes in the L1 trie + the sentinel
            num_blocks = max_batch * self.nbt + self.nbt + 1
        self.allocator = BlockAllocator(num_blocks, bs)
        self.trie = BlockTrie(bs)
        # ---- pressure-safe serving (PR 10) ---------------------------
        # preemption: when a step's alloc cannot be covered even by trie
        # eviction, demote a victim row's sealed KV to the host L2 and
        # requeue it (exact resume through warm admission).  Victims are
        # chosen least-progress first, latest-deadline tiebreak.
        if preempt_policy != "least_progress":
            raise ValueError(f"unknown preempt_policy {preempt_policy!r}")
        self.preempt_policy = preempt_policy
        # overcommit=True relaxes the chunked admission guarantee to the
        # PROMPT's blocks only: decode-time growth is served by
        # preemption instead of an up-front whole-lifetime reservation,
        # so an undersized pool oversubscribes and preempts rather than
        # rejecting at admission.
        self.overcommit = bool(overcommit)
        # deterministic fault injection (core.faults): threads the
        # "alloc" site into the allocator and the kvstore sites into the
        # recycler's store; "replica_step" fires in decode_batch.
        self.fault_plan = fault_plan
        if fault_plan is not None:
            self.allocator.fault_plan = fault_plan
            self.recycler.store.fault_plan = fault_plan
        # typed lifecycle events ("preempted" / "errored") for the
        # scheduler, drained once per step via drain_events()
        self._events: List[Tuple[str, dict]] = []
        self._preempted_now: set = set()
        # pending slots whose packed-segment descriptors are already
        # built this step: their blocks are about to be dispatched, so
        # they are not preemption victims until the dispatch lands
        self._pending_planned: set = set()
        # Under a mesh-carrying Runtime the pool is placed TP-sharded
        # (KV-head axis on 'model' when heads divide, replication fallback
        # otherwise — sharding.paged_pool_shardings); the attention
        # dispatches then run under shard_map (attention.paged_tp_axis).
        # Everything host-side (allocator, trie, table mirrors) is
        # replica-local numpy and never sees the mesh.
        self.pool = init_paged_pool(cfg, num_blocks, bs, max_batch,
                                    self.nbt, dtype=jnp.dtype(cfg.dtype),
                                    quant=self.kv_quant,
                                    fp_tail_blocks=fp_tail_blocks,
                                    mesh=self.rt.mesh)
        if prefill_mode not in ("chunked", "staged", "packed"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        self.prefill_mode = prefill_mode
        # semantic block-donor recycling (beyond paper; SemShareKV +
        # KVLink at block granularity): on a prefix miss, graft matching
        # interior donor blocks and recompute only the boundary.  The
        # mode rides the chunked admission's segment machinery — the
        # staged path has no segments, so the combination is rejected
        # rather than silently ignored.
        self.semantic = bool(getattr(self.recycler, "semantic", False))
        self.graft_max_div = graft_max_div
        self.semantic_gate_divs: List[float] = []
        if self.semantic and prefill_mode != "chunked":
            # packed admissions advance every pending chunk in one fused
            # dispatch and have no per-admission segment walk to ride
            raise ValueError("semantic grafting requires "
                             "prefill_mode='chunked'")
        if prefill_chunk is None:
            # default: 8 blocks per chunk.  Big enough that typical
            # admissions seal in one or two steps (and — for int8 pools —
            # that the fresh suffix usually lands in ONE chunk, whose
            # in-chunk attention is exact; history older than the fp ring
            # is read at int8 fidelity, see ROADMAP known limits), small
            # enough that a long prompt still yields the decode loop
            # between chunks.
            prefill_chunk = min(8 * bs, capacity)
        if prefill_chunk % bs or prefill_chunk <= 0:
            raise ValueError(
                f"prefill_chunk must be a positive multiple of the block "
                f"size {bs}, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # the FIXED ladder of chunk shapes: a step uses the smallest shape
        # covering its remaining suffix, so a 10-token warm-hit tail costs
        # a 2-block dispatch, not a full-width one.  The compile budget is
        # one executable per (shape, quant mode) — still independent of
        # how many distinct suffix lengths arrive.
        self.chunk_shapes = sorted({s for s in (bs, 2 * bs, prefill_chunk)
                                    if s <= prefill_chunk})
        # packed-admission buffer buckets: every pending admission's chunk
        # lands in ONE ragged buffer per engine step, padded to the
        # smallest bucket that fits.  One bucket per chunk shape (its
        # width times the worst-case segment count) keeps the compile
        # budget at <= len(chunk_shapes) executables per quant mode —
        # independent of suffix length AND concurrent-admission count.
        self.packed_buckets = sorted({c * self.max_batch
                                      for c in self.chunk_shapes})
        self.prealloc_watermark = prealloc_watermark
        # self-speculative decoding (PR 7): the same weights draft gamma
        # tokens against a sparse sink+recent block view — refined by
        # ``spec_iters`` fixed-point sweeps, each ONE multi-token
        # dispatch (see ``_draft_loop``) — then ONE batched multi-token
        # dispatch verifies the bundle against the full table.  Greedy
        # rows only — acceptance is longest-prefix match against the
        # greedy target, which keeps the output token-identical to the
        # plain step-by-step path.
        self.speculative = bool(speculative)
        self.gamma = int(gamma)
        self.sink_blocks = int(sink_blocks)
        self.recent_blocks = int(recent_blocks)
        self.spec_iters = int(spec_iters)
        if self.speculative:
            if self.gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            if self.spec_iters < 1:
                raise ValueError(
                    f"spec_iters must be >= 1, got {spec_iters}")
            if self.sink_blocks < 0 or self.recent_blocks < 1:
                raise ValueError("speculative drafting needs "
                                 "sink_blocks >= 0 and recent_blocks >= 1")
            if self.kv_quant and self.gamma > (fp_tail_blocks - 1) * bs:
                raise ValueError(
                    f"int8 speculative rollback requires gamma <= "
                    f"(fp_tail_blocks - 1) * block_size = "
                    f"{(fp_tail_blocks - 1) * bs}: a round writes ring "
                    f"positions [p, p + gamma], and the exact restore "
                    f"needs the pre-round snapshot to still cover every "
                    f"older block a wrapped write clobbered")
        # verify bundle width: gamma drafts + the pending token, padded
        # up to a block multiple (the verify kernel tiles by block)
        self.spec_cv = _ceil_div(self.gamma + 1, bs) * bs
        self.spec_ndt = self.sink_blocks + self.recent_blocks
        self._tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self._pos = jnp.zeros((max_batch,), jnp.int32)
        self._slots: List[Optional[_Slot]] = [None] * max_batch
        self._pending: Dict[int, _PendingAdmission] = {}
        self._tables = np.zeros((max_batch, self.nbt), np.int32)  # host mirror
        self._row_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        self._committed: List[int] = [0] * max_batch  # future allocs owed
        self._temp = np.zeros((max_batch,), np.float32)
        self._topk = np.zeros((max_batch,), np.int32)
        self._step_rng = self._sample_key

        self._stage_fn = jax.jit(_stage_from_pool, static_argnums=(2, 3))
        self._gather_q_fn = jax.jit(_gather_quant, static_argnums=(2, 3))
        self._scatter_fn = jax.jit(_scatter_to_pool,
                                   static_argnums=(3, 4, 5),
                                   donate_argnums=(0,))
        self._upload_fn = jax.jit(_upload_q8, static_argnums=(3,),
                                  donate_argnums=(0,))
        self._tail_fn = jax.jit(_fill_tail, donate_argnums=(0,))
        self._setrow_fn = jax.jit(_set_row, donate_argnums=(0, 1, 2))
        self._clear_fn = jax.jit(_clear_row, donate_argnums=(0,))
        self._pstep_fn = jax.jit(self._paged_step, donate_argnums=(1, 2, 3))
        self._pstep_sampled_fn = jax.jit(self._paged_step_sampled,
                                         donate_argnums=(1, 2, 3),
                                         static_argnums=(7,))
        # chunked-admission executables: ONE compiled prefill shape
        # (prefill_chunk is fixed; row / start / valid are traced scalars)
        self._chunk_fn = jax.jit(self._chunk_prefill, donate_argnums=(2,))
        # packed-admission executable: ONE dispatch advances EVERY pending
        # admission's chunk (ragged buffer + per-segment descriptors; one
        # compile per packed bucket)
        self._packed_fn = jax.jit(self._packed_prefill, donate_argnums=(2,))
        self._setents_batch_fn = jax.jit(_set_table_entries,
                                         donate_argnums=(0,))
        self._upload_blk_fn = jax.jit(_upload_fp_block, donate_argnums=(0,))
        self._upload_q8_blk_fn = jax.jit(_upload_q8_block,
                                         donate_argnums=(0,))
        self._settail_fn = jax.jit(_set_row_tail, donate_argnums=(0,))
        self._seedtail_fn = jax.jit(_seed_tail_from_pool,
                                    donate_argnums=(0,))
        # speculative executables: the whole draft loop is ONE dispatch
        # (spec_iters unrolled fixed-point sweeps over all gamma
        # positions), verification another — a round costs two
        # dispatches regardless of batch size or gamma.  The draft
        # only READS the pool (one view gather), so nothing is donated
        self._draft_fn = jax.jit(self._draft_loop)
        self._verify_fn = jax.jit(self._verify_step, donate_argnums=(2,))
        # a REAL device copy: the verify dispatch donates the pool, so a
        # mere reference to the live tails would be invalidated
        self._snap_fn = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        self._ringfix_fn = jax.jit(_ring_restore, donate_argnums=(0,))
        self.stats.update({
            "batched_decode_steps": 0, "admissions": 0, "sampled_steps": 0,
            "resident_hits": 0, "host_promotions": 0, "cow_copies": 0,
            "h2d_copies": 0, "h2d_bytes": 0, "trie_evictions": 0,
            "layout_conversions": 0,
            "q8_block_promotions": 0, "prefill_chunks": 0,
            "prefill_packed_steps": 0, "prefill_dispatches": 0,
            "staging_prefills": 0, "spec_preallocs": 0,
            "spec_rounds": 0, "spec_draft_tokens": 0,
            "spec_accepted_tokens": 0, "spec_emitted_tokens": 0,
            "spec_fallback_steps": 0,
            "semantic_grafts": 0, "semantic_refusals": 0,
            "semantic_resident_grafts": 0, "semantic_host_grafts": 0,
            "tokens_grafted": 0,
            "preemptions": 0, "preempted_tokens_recomputed": 0,
            "step_rollbacks": 0, "preempt_errors": 0,
            # blocking device -> host reads on the serving path (_wait)
            "host_syncs": 0,
            # decode dispatches: table entries the paged decode kernel
            # walks for the decoding rows (through each row's last valid
            # page), and the entries their tables hold
            "decode_pages_walked": 0, "decode_table_pages": 0,
        })

    # ------------------------------------------------------------------
    def _make_cache(self, capacity: int):
        """Admission staging is always full precision — the int8 pool
        quantizes at the scatter boundary (once per token), never inside
        the prefill — so one staging layout serves both pool tiers."""
        return init_cache(self.cfg, 1, capacity, window=self.window,
                          dtype=jnp.dtype(self.cfg.dtype), kv_quant=False)

    def _wait(self, what: str):
        """The span around one blocking device -> host read, counted in
        ``stats["host_syncs"]``; ``what`` names the read site."""
        self.stats["host_syncs"] += 1
        return span("engine.wait", what=what)

    def _host_layout_ok(self, cache) -> bool:
        """A host entry is promotable iff it materializes to the plain fp
        staging layout.  Entries admitted by the dense ``kv_quant``
        engines carry native int8 + k_scale leaves the staged prefill
        can't consume — honest miss instead of corrupting the pool."""
        return not any(isinstance(c, dict) and "k_scale" in c
                       for c in cache.values())

    def _q8_blocks(self, raw, depth: int) -> int:
        """How many FULL blocks of a quantized host entry's int8 region
        cover [0, depth) — the part of a promotion that moves verbatim."""
        if raw is None or not kvq.is_quantized(raw):
            return 0
        for c in raw.values():
            leaf = c.get("k") if isinstance(c, dict) else None
            if isinstance(leaf, dict) and kvq._QKEY in leaf:
                if "ax" not in leaf:
                    # legacy quantized entry (pre-residual format): no
                    # verbatim upload — fall back to dequant + scatter
                    return 0
                ax = int(np.asarray(leaf["ax"]))
                split = leaf[kvq._QKEY].shape[ax]
                return min(split, depth) // self.block
        return 0

    def _slice_q8(self, raw, n8: int):
        """Host-side view of a quantized entry's first ``n8`` positions in
        the pool's upload layout: int8 codes + f32 scales (keepdim
        dropped), per segment.  Pure slicing — no arithmetic touches the
        stored bits."""
        ent = {}
        for seg, c in raw.items():
            sub = {}
            for name in ("k", "v"):
                leaf = c[name]
                ax = int(np.asarray(leaf["ax"]))
                sl = [slice(None)] * leaf[kvq._QKEY].ndim
                sl[ax] = slice(0, n8)
                sub[name] = jnp.asarray(leaf[kvq._QKEY][tuple(sl)])
                sub[name + "_scale"] = jnp.asarray(
                    np.asarray(leaf["scale"])[tuple(sl)][..., 0])
            ent[seg] = sub
        return ent

    def _harvest(self, chain_ids, depth: int, cap: int):
        """Gather pool blocks [0, depth) into a host-store entry.  fp
        pools return the dense staging layout; int8 pools return the
        quantized-tree host format built from the pool's VERBATIM int8
        codes (plus a dequantized fp residual tail), so the quantization
        the blocks received at their seal is the only one they ever get."""
        if not self.kv_quant:
            staged = self._stage_fn(self.pool, chain_ids, depth, cap)
            with self._wait("harvest"):
                return to_host(staged)
        gathered = self._gather_q_fn(self.pool, chain_ids, depth, cap)
        with self._wait("harvest"):
            g = to_host(gathered)
        residual = self.recycler.compress_residual
        split = max(0, depth - residual)
        dt = jnp.dtype(self.cfg.dtype)
        entry = {}
        for seg, c in g.items():
            sub = {"slot_pos": c["slot_pos"]}
            for name in ("k", "v"):
                q = c[name]                        # (L, 1, cap, H, D) int8
                s = c[name + "_scale"]             # (L, 1, cap, H) f32
                tail = (q[:, :, split:depth].astype(np.float32)
                        * s[:, :, split:depth, :, None]).astype(dt)
                sub[name] = {
                    kvq._QKEY: q[:, :, :split],
                    "scale": s[:, :, :split, :, None],
                    "dtype": np.dtype(dt).str,
                    "tail": tail,
                    "cap": np.int64(cap),
                    "ax": np.int64(2),
                }
            entry[seg] = sub
        return entry

    # ------------------------------------------------------------------
    def _paged_step(self, params, tokens, pool, pos):
        # greedy via the engine module so tests can substitute it (early
        # EOS) in the serial, dense-pool and paged paths at once
        logits, pool = decode_step(self.cfg, params, tokens, pool, pos,
                                   window=0, rt=self.rt)
        nxt = engine_mod.greedy(logits)
        return nxt, nxt[:, None], pool, pos + 1

    def _paged_step_sampled(self, params, tokens, pool, pos, temp, topk,
                            rng, topk_cap):
        logits, pool = decode_step(self.cfg, params, tokens, pool, pos,
                                   window=0, rt=self.rt)
        nxt = sample_batched(logits, rng, temperature=temp, top_k=topk,
                             top_k_cap=topk_cap)
        return nxt, nxt[:, None], pool, pos + 1

    def _chunk_prefill(self, params, tokens, pool, row, table_row, c0,
                       w_floor, n_valid):
        return prefill_paged(self.cfg, params, tokens, pool, row,
                             table_row, c0, w_floor, n_valid, rt=self.rt)

    def _packed_prefill(self, params, tokens, pool, rows, tables, c0s,
                        w_floors, valids, q_offs, seg_ids):
        return prefill_paged_packed(self.cfg, params, tokens, pool, rows,
                                    tables, c0s, w_floors, valids, q_offs,
                                    seg_ids, rt=self.rt)

    # ------------------------------------------------------------------
    # self-speculative decoding (drafter == target; sparse-view draft)
    # ------------------------------------------------------------------
    def _draft_loop(self, params, tok0, pool, pos, dtab, dbase):
        """``gamma`` greedy sparse-view drafts in ONE dispatch: the same
        weights decode attending only the sink + recent pool blocks
        named by ``dtab``/``dbase`` (positions stay truthful through the
        base indices).  The pool is READ-ONLY here — its sparse view is
        gathered ONCE up front — and the guesses are refined by
        ``spec_iters`` FIXED-POINT sweeps (``draft_refine``), each a
        single multi-token forward over all gamma positions, instead of
        gamma sequential one-token decodes: after k sweeps the first k
        drafts equal exact sequential greedy over the view, and
        predictable spans converge much faster, so a whole round costs
        spec_iters + 1 bundle-sized dispatches.  Drafts write nothing:
        the verify pass encodes every round position itself, so drafts
        only ever influence which tokens get PROPOSED, never what the
        pool ends up holding."""
        view, vpos = draft_view(self.cfg, pool, dtab, dbase, pos)
        guess = jnp.tile(tok0, (1, self.gamma))
        for _ in range(self.spec_iters):
            toks = jnp.concatenate([tok0, guess[:, :-1]], axis=1)
            logits = draft_refine(self.cfg, params, toks, view, vpos,
                                  pos, rt=self.rt)
            # greedy via the engine module: tests substitute it (early
            # EOS), and the drafter must propose with the same rule the
            # verifier accepts by
            guess = engine_mod.greedy(logits)
        return guess

    def _verify_step(self, params, tokens, pool, snap, c0s, act):
        """ONE batched dispatch verifying every armed row's bundle (the
        pending token + its gamma drafts) against the FULL table,
        returning the greedy target at every bundle position.  int8
        pools attend their fp recent window from the pre-round ring
        SNAPSHOT — taken anyway for the exact rollback restore, and
        identical to the live ring since drafts stopped touching the
        pool; the snapshot rides into the layer scan as extra cache
        leaves and is stripped before the pool comes back."""
        if snap is not None:
            pool = {seg: {**c, "k_tail_snap": snap[seg]["k_tail"],
                          "v_tail_snap": snap[seg]["v_tail"]}
                    for seg, c in pool.items()}
        logits, pool = verify_paged(self.cfg, params, tokens, pool, c0s,
                                    jnp.int32(self.gamma + 1), act,
                                    rt=self.rt)
        return engine_mod.greedy(logits), pool

    def _draft_tokens(self, draft):
        """The draft tokens fed to verification — a patchable seam: the
        rollback property test substitutes ARBITRARY tokens here, because
        acceptance must reproduce the non-speculative output whatever the
        drafter proposed (drafts only affect speed, never tokens)."""
        return draft

    def _spec_ready(self, active) -> bool:
        """A speculative round replaces this step iff every active row
        decodes greedily (acceptance is longest-prefix match against the
        greedy target), every row's gamma + 1 bundle positions fit its
        capacity, and the round's reserved blocks are obtainable."""
        if np.any(self._temp > 0.0):
            return False
        bs = self.block
        need = 0
        for i in active:
            st = self._slots[i]
            p = st.m + len(st.emitted) - 1
            if p + self.gamma > self.capacity - 1:
                return False
            need += sum(1 for idx in range(p // bs,
                                           (p + self.gamma) // bs + 1)
                        if self._tables[i, idx] == SENTINEL)
        return self.allocator.num_free() + self._evictable() >= need

    def _spec_round(self, active):
        """One speculative round over the armed rows: reserve the
        bundle's blocks, snapshot the fp ring (int8), draft gamma tokens
        against the sparse sink+recent view, verify the bundle in one
        batched full-table dispatch, emit the accepted prefix plus the
        free bonus token, and roll the rejected tail back (table
        truncation + refcount release + exact ring restore).  Token-for-
        token identical to plain greedy steps — the drafts only decide
        how many of those steps one round buys."""
        bs = self.block
        B, g = self.max_batch, self.gamma
        W = self.nbt + 2 * self.max_batch
        ps_h: Dict[int, int] = {}
        reserved: Dict[int, List[Tuple[int, int]]] = {}
        updates: List[Tuple[int, int, int]] = []
        for i in active:
            st = self._slots[i]
            p = st.m + len(st.emitted) - 1
            ps_h[i] = p
            rs = []
            for idx in range(p // bs, (p + g) // bs + 1):
                if self._tables[i, idx] == SENTINEL:
                    b = self._alloc_block()
                    self._tables[i, idx] = b
                    self._row_blocks[i].append(b)
                    self._committed[i] -= 1
                    updates.append((i, idx, b))
                    rs.append((idx, b))
            reserved[i] = rs
        while updates:
            self._apply_table_updates(updates[:W])
            updates = updates[W:]

        snap = None
        if self.kv_quant:
            snap = self._snap_fn({seg: {"k_tail": c["k_tail"],
                                        "v_tail": c["v_tail"]}
                                  for seg, c in self.pool.items()})

        # sparse draft view: first sink_blocks table entries + the
        # recent window ending at the round's last reserved block, with
        # the ORIGINAL table indices alongside so kv positions stay
        # truthful; -1 bases mark padding
        dtab = np.zeros((B, self.spec_ndt), np.int32)      # SENTINEL pad
        dbase = np.full((B, self.spec_ndt), -1, np.int32)
        pos_h = np.zeros((B,), np.int32)
        tok0 = np.zeros((B, 1), np.int32)
        act_h = np.zeros((B,), np.int32)
        for i in active:
            p = ps_h[i]
            hi = (p + g) // bs
            lo = max(0, hi - self.recent_blocks + 1)
            idxs = (list(range(min(self.sink_blocks, lo)))
                    + list(range(lo, hi + 1)))
            for j, idx in enumerate(idxs):
                dtab[i, j] = self._tables[i, idx]
                dbase[i, j] = idx
            pos_h[i] = p
            tok0[i, 0] = self._slots[i].emitted[-1]
            act_h[i] = 1

        dts = self._draft_fn(
            self.params, jnp.asarray(tok0), self.pool,
            jnp.asarray(pos_h), jnp.asarray(dtab), jnp.asarray(dbase))
        with self._wait("draft"):
            draft = self._draft_tokens(np.asarray(dts))

        vt = np.zeros((B, self.spec_cv), np.int32)
        for i in active:
            vt[i, 0] = self._slots[i].emitted[-1]
            vt[i, 1:g + 1] = draft[i]
        tg, self.pool = self._verify_fn(
            self.params, jnp.asarray(vt), self.pool, snap,
            jnp.asarray(pos_h), jnp.asarray(act_h))
        with self._wait("verify"):
            targets = np.asarray(tg)

        done: List[Tuple[int, GenResult]] = []
        roll_updates: List[Tuple[int, int, int]] = []
        roll_free: List[int] = []
        fix_ps = np.full((B,), -(2 ** 30), np.int32)  # default: keep all
        fix_fbs = np.zeros((B,), np.int32)
        self.stats["spec_rounds"] += 1
        self.stats["spec_draft_tokens"] += g * len(active)
        for i in active:
            st = self._slots[i]
            p = ps_h[i]
            # greedy acceptance: longest prefix of drafts matching the
            # verifier's targets, then the target at the first mismatch
            # (or past the last draft) rides along free
            a = 0
            while a < g and draft[i, a] == targets[i, a]:
                a += 1
            self.stats["spec_accepted_tokens"] += a
            burst = [int(x) for x in draft[i, :a]] + [int(targets[i, a])]
            n_emit = 0
            finished = False
            for t in burst:
                st.emitted.append(t)
                n_emit += 1
                if ((st.stop_at_eos and t == EOS)
                        or len(st.emitted) >= st.max_new):
                    finished = True
                    break
            self.stats["spec_emitted_tokens"] += n_emit
            if finished:
                done.append((i, self._result(st, row=i)))
                self._release_row(i)
                continue
            # rollback: reserved blocks past the accept frontier leave
            # the table and return to the free list; verify's writes at
            # the kept positions [p, p + a] stay (they are the exact
            # values plain decode would have written)
            fb = (p + a) // bs
            dropped = [(idx, b) for idx, b in reserved[i] if idx > fb]
            if dropped:
                for idx, b in dropped:
                    self._tables[i, idx] = SENTINEL
                    roll_updates.append((i, idx, SENTINEL))
                    roll_free.append(b)
                    self._committed[i] += 1
                self._row_blocks[i] = [int(x) for x in self._tables[i]
                                       if x != SENTINEL]
            fix_ps[i] = p
            fix_fbs[i] = fb
        while roll_updates:
            self._apply_table_updates(roll_updates[:W])
            roll_updates = roll_updates[W:]
        if roll_free:
            self.allocator.unref_many(roll_free)
        if self.kv_quant:
            # exact ring restore (see _ring_restore): finished rows keep
            # their stale rings — the next admission reseeds them before
            # any query can gate them in
            self.pool = self._ringfix_fn(self.pool, snap,
                                         jnp.asarray(fix_ps),
                                         jnp.asarray(fix_fbs))
        # rebuild the device token/pos mirrors wholesale: every
        # surviving row advanced a different number of tokens this round
        tok_h = np.zeros((B, 1), np.int32)
        npos_h = np.zeros((B,), np.int32)
        for i in self.active_slots():
            st = self._slots[i]
            tok_h[i, 0] = st.emitted[-1]
            npos_h[i] = st.m + len(st.emitted) - 1
        self._tokens = jnp.asarray(tok_h)
        self._pos = jnp.asarray(npos_h)
        return done

    def prefill_compiles(self) -> int:
        """How many prefill executables the admission path has compiled.
        The chunked path's whole point is that this is bounded by
        ``len(self.chunk_shapes)`` (one per fixed chunk shape) —
        independent of how many distinct suffix lengths were admitted —
        where the staged path compiles one per (suffix length, capacity
        bucket).  The packed path is bounded by ``len(self.packed_buckets)``
        — also independent of how many admissions ran CONCURRENTLY."""
        fn = {"chunked": self._chunk_fn,
              "packed": self._packed_fn}.get(self.prefill_mode,
                                             self._prefill_fn)
        return fn._cache_size()

    # ------------------------------------------------------------------
    def _convert_dense_quant(self, cache):
        """A host entry admitted by the dense ``kv_quant`` engines carries
        native int8 K/V + per-vector scale leaves the staged/chunked
        admission layouts can't consume directly.  Dequantize it to the
        plain fp staging layout (value-preserving to within half a quant
        step) so the entry still promotes instead of being skipped — the
        honest fix for the old ``layout_skips`` gap."""
        dt = jnp.dtype(self.cfg.dtype)
        out = {}
        for seg, c in cache.items():
            if isinstance(c, dict) and "k_scale" in c:
                sub = {"slot_pos": c["slot_pos"]}
                for name in ("k", "v"):
                    q = np.asarray(c[name], np.float32)
                    s = np.asarray(c[name + "_scale"], np.float32)
                    sub[name] = (q * s[..., None]).astype(dt)
                out[seg] = sub
            else:
                out[seg] = c
        return out

    def _lookup_tiers(self, prompt: str, ids, m: int):
        """Serve an admission from the cache tiers: L1 (device-resident
        block trie) preferred, L2 (host store) behind it.  Returns
        (depth, hit, mode, sim, chain, res, host_cache) where host_cache
        is the promotable staging-layout view of the L2 entry (converted
        from the dense kv_quant layout when necessary)."""
        d1, chain = self.trie.lookup(ids)
        d1 = min(d1, m - 1)
        d2, res = 0, None
        sim = 0.0
        if d1 < m - 1:
            # L1 can still be beaten — consult the host (L2) tier.  At
            # maximal resident depth the lookup is skipped: no host hit
            # (d2 <= m-1) could win, and Recycler.lookup would materialize
            # the whole host cache just to be discarded.
            res = self.recycler.lookup(prompt, ids)
            if res.hit:
                d2 = res.reuse_depth
            sim = res.similarity
        # prefer the resident tier unless the host hit is deeper by MORE
        # than one block: re-prefilling a partial-block tail is far
        # cheaper than a host→device copy of the whole prefix
        if d1 > 0 and d1 >= d2 - self.block:
            # a resident hit is served by the trie, not retrieval — there
            # is no honest similarity to report
            self.stats["resident_hits"] += 1
            return d1, True, "resident_block", float("nan"), chain, res, None
        if d2 > 0:
            # lazy layout conversion — only once the host tier actually
            # WON the comparison, so a resident hit never pays (or counts)
            # a conversion it would discard
            host_cache = res.cache
            if not self._host_layout_ok(host_cache):
                host_cache = self._convert_dense_quant(host_cache)
                self.stats["layout_conversions"] += 1
            self.stats["host_promotions"] += 1
            return d2, True, res.mode, sim, [], res, host_cache
        return 0, False, "miss", sim, [], res, None

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots)
                if s is None and i not in self._pending]

    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    def pending_admissions(self) -> List[int]:
        return sorted(self._pending)

    # ------------------------------------------------------------------
    # block bookkeeping
    # ------------------------------------------------------------------
    def _evictable(self, exclude=()) -> int:
        ex = set(exclude)
        return self.trie.evictable(
            lambda b: b not in ex and self.allocator.refcount(b) == 1)

    def _alloc_block(self, protect=()) -> int:
        """A fresh private block, evicting cold L1 prefixes if needed.
        ``protect`` names blocks that must survive eviction (e.g. the
        boundary block an in-progress admission is about to gather)."""
        try:
            return self.allocator.alloc()
        except BlockPoolExhausted:
            ex = set(protect)
            dropped = self.trie.evict(
                1, lambda b: b not in ex and self.allocator.refcount(b) == 1)
            for b in dropped:
                self.allocator.unref(b)
                self.stats["trie_evictions"] += 1
            if not dropped:
                raise
            return self.allocator.alloc()

    # ------------------------------------------------------------------
    # preemption with exact resume (PR 10)
    # ------------------------------------------------------------------
    def drain_events(self) -> List[Tuple[str, dict]]:
        """Typed lifecycle events since the last drain.  ``("preempted",
        payload)`` carries everything ``admit_slot(resume=payload)``
        needs to resume the request token-identically; ``("errored",
        payload)`` reports a request the engine had to drop (payload has
        ``slot`` / ``error``)."""
        ev, self._events = self._events, []
        return ev

    def _alloc_pressure(self, protect=(), exclude_rows=(),
                        exclude_pending=()) -> int:
        """``_alloc_block`` that converts exhaustion into preemption:
        while no block is obtainable, demote one victim (least-progress
        first, latest-deadline tiebreak) and retry.  Raises
        BlockPoolExhausted only once no eligible victim remains — the
        caller then preempts/cancels ITSELF, so the exception never
        escapes an engine step."""
        while True:
            try:
                return self._alloc_block(protect=protect)
            except BlockPoolExhausted:
                if not self._try_preempt(exclude_rows=exclude_rows,
                                         exclude_pending=exclude_pending):
                    raise

    def _try_preempt(self, exclude_rows=(), exclude_pending=()) -> bool:
        """Pick and preempt ONE victim.  Candidates: decoding rows
        (demoted to L2 + requeued) and block-holding pending admissions
        (cancelled + requeued; their sealed chunks stay warm in the L1
        trie).  Grafted rows are never victims — their approximate
        blocks must not reach the host store (contamination rule), and a
        re-admission could gate differently."""
        cands = []
        for i, st in enumerate(self._slots):
            if st is None or i in exclude_rows or i in self._preempted_now:
                continue
            if st.mode == "semantic_block":
                continue
            dl = st.deadline_t if st.deadline_t is not None else float("inf")
            cands.append((len(st.emitted), -dl, 1, i))
        for s, adm in self._pending.items():
            if (s in exclude_pending or s in self._pending_planned
                    or s in self._preempted_now
                    or not self._row_blocks[s] or adm.graft is not None):
                continue
            st = adm.st
            dl = st.deadline_t if st.deadline_t is not None else float("inf")
            cands.append((0, -dl, 0, s))
        if not cands:
            return False
        cands.sort()
        _, _, kind, s = cands[0]
        if kind == 1:
            self._preempt_row(s)
        else:
            st = self._cancel_admission(s)
            payload = self._resume_payload(st, [])
            payload["slot"] = s
            self._events.append(("preempted", payload))
            self.stats["preemptions"] += 1
        self._preempted_now.add(s)
        return True

    def _resume_payload(self, st: _Slot, extra) -> dict:
        """Everything ``admit_slot(resume=...)`` needs to continue this
        request exactly where it stopped.  ``extra`` is the tokens this
        residency emitted (appended to the resume prompt so the warm
        re-admission re-derives the next token from the same state)."""
        extra = list(extra)
        ids = (np.concatenate([st.ids, np.asarray(extra, np.int32)])
               if extra else st.ids)
        return {
            "prompt": st.prompt, "ids": ids,
            "emitted": list(st.resume_emitted) + extra,
            "max_new_left": st.max_new - len(extra),
            "preemptions": st.preemptions + 1,
            "tokens_recomputed": st.tokens_recomputed,
            "t0": st.t0, "t_first": st.t_first,
            "temperature": st.temperature, "top_k": st.top_k,
            "tenant": st.tenant, "use_recycling": st.use_recycling,
            "admit": st.admit, "stop_at_eos": st.stop_at_eos,
        }

    def _mark_resumed(self, st: _Slot, resume: dict) -> None:
        """Carry a preempted request's cross-residency state onto its
        resumed slot: previously emitted tokens (prepended to the final
        result), preemption counters, and the ORIGINAL t0 / t_first so
        latency and TTFT describe the request, not its last residency."""
        st.resume_emitted = list(resume["emitted"])
        st.preemptions = int(resume["preemptions"])
        st.tokens_recomputed = int(resume.get("tokens_recomputed", 0))
        st.t0 = float(resume["t0"])
        if resume.get("t_first"):
            st.t_first = float(resume["t_first"])

    def _preempt_row(self, row: int) -> None:
        """Demote a decoding row and requeue it.  Sealed KV [0, p) —
        p = m + k - 1, since the last emitted token's KV is not written
        until the next step — is harvested to the host L2 (int8 pools
        keep their codes verbatim, with the live fp ring overlaid into
        the entry's residual tail so the resume reseeds an EXACT ring);
        trie-resident prompt blocks just drop the row's reference and
        stay warm.  The resume payload re-admits prompt + all emitted
        tokens through the ordinary warm admission machinery, which is
        what makes a preempted-then-resumed greedy run token-identical
        to an uninterrupted one."""
        with span("engine.preempt"):
            st = self._slots[row]
            p = st.m + len(st.emitted) - 1
            if st.use_recycling and p > 0:
                cap = self._capacity(st.m + st.max_new)
                chain = [b for b in self._tables[row]
                         if b != SENTINEL][:_ceil_div(p, self.block)]
                entry = self._harvest(jnp.asarray(chain, jnp.int32), p,
                                      cap)
                if self.kv_quant:
                    self._overlay_ring_tail(entry, row, p)
                ids_sealed = np.concatenate(
                    [st.ids, np.asarray(st.emitted[:-1], np.int32)])
                self.recycler.admit(st.prompt, ids_sealed, entry, p, cap,
                                    tenant=st.tenant)
            payload = self._resume_payload(st, st.emitted)
            payload["slot"] = row
            self._release_row(row)
            self._events.append(("preempted", payload))
            self.stats["preemptions"] += 1

    def _overlay_ring_tail(self, entry, row: int, depth: int) -> None:
        """Overlay the row's LIVE fp ring values into a harvest entry's
        residual tail.  ``_harvest`` rebuilds the tail by dequantizing
        the sealed int8 codes, but an uninterrupted run attends its last
        R blocks from the exact fp ring — without this overlay a resumed
        row would read dequant-precision recents where the uninterrupted
        run reads exact ones, breaking token identity.  Positions older
        than the ring keep the dequantized values (both runs read those
        through the int8 codes, so they agree by construction)."""
        bs = self.block
        R = self.fp_tail_blocks
        residual = self.recycler.compress_residual
        split = max(0, depth - residual)
        fb = (depth - 1) // bs
        lo = max(max(0, fb - R + 1) * bs, split)
        for seg, sub in entry.items():
            for name in ("k", "v"):
                with self._wait("ring_tail"):
                    ring = np.asarray(self.pool[seg][name + "_tail"][:, row])
                tail = sub[name]["tail"]          # (L, 1, depth-split, H, D)
                for q in range(lo, depth):
                    off = (q // bs % R) * bs + q % bs
                    tail[:, 0, q - split] = ring[:, off]

    def _cancel_admission(self, slot: int) -> _Slot:
        """Roll a pending admission back to a clean, invariant-true
        state: the row's blocks are dereferenced (chunks already sealed
        AND registered stay warm through the trie's own references), the
        mirrors are cleared, and the slot is free again.  Returns the
        slot record so the caller can requeue or error the request."""
        adm = self._pending.pop(slot)
        # unref from the TABLE mirror, not _row_blocks: a chunk's alloc
        # loop updates the table block-by-block and only syncs
        # _row_blocks after the loop — cancelling mid-loop must still
        # free the blocks the partial loop grabbed
        for x in self._tables[slot]:
            if x != SENTINEL:
                self.allocator.unref(int(x))
        self._row_blocks[slot] = []
        self._committed[slot] = 0
        self._tables[slot] = SENTINEL
        self.pool = self._clear_fn(self.pool, slot)
        return adm.st

    def _self_preempt(self, row: int) -> None:
        """Last resort when a row's own decode write cannot be covered
        even after preempting every eligible victim: demote THIS row
        (or, for a grafted row — which must never be demoted — error it
        out) so ``BlockPoolExhausted`` never escapes the step."""
        with span("engine.preempt"):
            st = self._slots[row]
            if st.mode == "semantic_block":
                self._events.append(("errored", {
                    "slot": row, "prompt": st.prompt, "tenant": st.tenant,
                    "error": "pool exhausted: grafted row cannot be "
                             "demoted"}))
                self.stats["preempt_errors"] += 1
                self._release_row(row)
            else:
                self._preempt_row(row)
            self._preempted_now.add(row)

    def device_kv_bytes_in_use(self) -> int:
        """Bytes of pool K/V actually referenced (live blocks, counted
        once however many tables share them).  In int8 mode a block costs
        int8 K/V + per-vector f32 scales — the ~2-4x reduction this
        returns vs an fp pool is the whole point of the tier; the per-row
        fp ring tails are a constant (max_batch-sized) overhead, not a
        per-block cost, and are excluded."""
        return self.allocator.num_live() * paged_block_bytes(
            self.cfg, self.block, dtype=jnp.dtype(self.cfg.dtype),
            quant=self.kv_quant)

    def kv_tp_degree(self) -> int:
        """How many 'model' shards the pool's KV-head axis is split over
        (1 when unsharded or when the replication fallback applied)."""
        from repro.models.attention import paged_tp_axis
        ax = paged_tp_axis(self.rt, {"k": self.pool["seg0"]["k"][0]})
        return 1 if ax is None else self.rt.mesh.shape[ax]

    def device_kv_bytes_per_device(self) -> int:
        """Live pool bytes each device holds: the TP shards split the
        KV-head axis, so per-device bytes are in_use / tp."""
        return self.device_kv_bytes_in_use() // self.kv_tp_degree()

    # ------------------------------------------------------------------
    def admit_slot(self, slot: int, prompt: str, *,
                   max_new_tokens: Optional[int] = None,
                   use_recycling: bool = True, admit: bool = False,
                   stop_at_eos: bool = True, temperature: float = 0.0,
                   top_k: int = 0,
                   tenant: Optional[str] = None,
                   deadline_t: Optional[float] = None,
                   resume: Optional[dict] = None) -> Optional[GenResult]:
        """Admit ``prompt`` into pool row ``slot``.

        ``prefill_mode="chunked"`` (default): the admission is queued as a
        sequence of fixed-size chunk steps that ``decode_batch`` advances
        one per engine step, interleaved with the batched decode dispatch.
        Each chunk writes its K/V straight into freshly allocated pool
        blocks and attends through the block table — the staging cache,
        the resident-prefix gather and the post-prefill scatter of the
        staged path do not exist on this route, and one compiled prefill
        executable PER FIXED CHUNK SHAPE serves every suffix length.

        ``prefill_mode="packed"`` queues the admission identically, but
        ``decode_batch`` advances ALL pending admissions' chunks in ONE
        ragged packed dispatch per step (``_admission_step_packed``).

        ``prefill_mode="staged"`` keeps the original path (one dense
        prefill over a full-capacity staging cache, gathered from /
        scattered back to the pool) as the reference baseline."""
        if self._slots[slot] is not None or slot in self._pending:
            raise ValueError(f"slot {slot} is occupied")
        t0 = time.perf_counter()
        if resume is not None:
            # resume of a preempted request: the "prompt" token stream is
            # the original prompt + every token already emitted, so the
            # warm admission re-derives the next token from exactly the
            # state the preemption froze
            ids = np.asarray(resume["ids"], np.int32)
            m = len(ids)
            max_new = int(resume["max_new_left"])
        else:
            max_new = max_new_tokens or self.max_new
            ids = self.tok.encode(prompt)
            m = len(ids)
        if m + max_new > self.capacity:
            raise AdmissionRejected(
                f"request needs {m + max_new} positions; pool "
                f"capacity is {self.capacity}")
        if self.prefill_mode in ("chunked", "packed"):
            return self._admit_chunked(slot, prompt, ids, m, max_new,
                                       use_recycling, admit, stop_at_eos,
                                       temperature, top_k, t0, tenant,
                                       deadline_t=deadline_t, resume=resume)
        return self._admit_staged(slot, prompt, ids, m, max_new,
                                  use_recycling, admit, stop_at_eos,
                                  temperature, top_k, t0, tenant,
                                  deadline_t=deadline_t, resume=resume)

    def _admit_staged(self, slot: int, prompt: str, ids, m: int,
                      max_new: int, use_recycling: bool, admit: bool,
                      stop_at_eos: bool, temperature: float, top_k: int,
                      t0: float, tenant: Optional[str] = None,
                      deadline_t: Optional[float] = None,
                      resume: Optional[dict] = None) -> Optional[GenResult]:
        """The PR-2 admission path: L1 block-table reuse when the prefix
        is device-resident, else L2 host promotion, else a cold prefill —
        all through one staged dense prefill whose result is scattered
        into (copy-on-write) private blocks.  Kept as the equivalence
        reference for the chunked path."""
        bs = self.block
        nb_prompt = _ceil_div(m, bs)
        nb_total = _ceil_div(m + max_new, bs)

        depth, hit, mode, sim = 0, False, "baseline", 0.0
        chain: List[Tuple[int, int]] = []
        res, host_cache = None, None
        if use_recycling:
            depth, hit, mode, sim, chain, res, host_cache = \
                self._lookup_tiers(prompt, ids, m)

        nb_shared = depth // bs if chain else 0
        start = nb_shared * bs               # first position written fresh
        shared = [b for b, _ in chain[:nb_shared]]
        gather = [b for b, _ in chain[:_ceil_div(depth, bs)]] if chain else []

        # admission guarantee: every block this request will ever need —
        # now or at a later decode boundary — must be obtainable without
        # starving the futures other in-flight rows were promised
        need_now = nb_prompt - nb_shared
        need_later = nb_total - nb_prompt
        owed = sum(self._committed)
        avail = self.allocator.num_free() + self._evictable(exclude=gather)
        if avail < need_now + need_later + owed:
            msg = (
                f"paged pool exhausted: request needs {need_now + need_later}"
                f" blocks, {avail - owed} obtainable "
                f"(free={self.allocator.num_free()}, "
                f"in-flight reservations={owed})")
            if self.active_slots() or self._pending:
                # in-flight rows will free blocks — transient, retry later
                raise PoolSaturated(msg)
            raise AdmissionRejected(msg)

        for b in shared:                      # share the resident prefix
            self.allocator.ref(b)
        fresh: List[int] = []
        try:
            for _ in range(need_now):
                fresh.append(self._alloc_block(protect=gather))
        except (BlockPoolExhausted, InjectedFault) as e:
            # contained rollback: the guarantee above held, so this can
            # only be an injected/transient fault — undo the partial grab
            # and report saturation so the scheduler retries
            for b in fresh:
                self.allocator.unref(b)
            for b in shared:
                self.allocator.unref(b)
            self.stats["step_rollbacks"] += 1
            raise PoolSaturated(str(e)) from e
        if chain and depth % bs:
            # divergent boundary block: its private copy is written from
            # staging below instead of mutating the shared original
            self.stats["cow_copies"] += 1

        # ---- staged dense prefill (the compiled serial path) ----------
        cap = self._capacity(m)
        if mode == "resident_block":
            stage = self._stage_fn(self.pool, jnp.asarray(gather, jnp.int32),
                                   depth, cap)
        elif hit:
            self.stats["h2d_copies"] += 1
            self.stats["h2d_bytes"] += tree_bytes(host_cache)
            stage = jax.tree.map(jnp.asarray, grow_capacity(host_cache, cap))
        else:
            stage = self._make_cache(cap)
        suffix = jnp.asarray(ids[depth:])[None]
        logits, stage = self._prefill_fn(self.params, suffix, stage, depth)
        self.stats["staging_prefills"] += 1
        self.stats["prefill_dispatches"] += 1

        # ---- scatter the fresh region [start, m) into private blocks --
        # A quantized host entry's full int8 blocks are promoted verbatim
        # (_upload_q8, no requant); everything after them — the entry's fp
        # residual tail, the sub-block remainder, the fresh suffix — is
        # quantized here, at its one scatter.
        if fresh:
            up = (self._q8_blocks(res.entry.cache, depth)
                  if self.kv_quant and hit and mode != "resident_block"
                  and res is not None and res.entry is not None else 0)
            if up:
                self.pool = self._upload_fn(
                    self.pool, self._slice_q8(res.entry.cache, up * bs),
                    jnp.asarray(fresh[:up], jnp.int32), bs)
                self.stats["q8_block_promotions"] += up
            s0 = start + up * bs             # start == 0 on the host path
            self.pool = self._scatter_fn(
                self.pool, stage, jnp.asarray(fresh[up:], jnp.int32),
                s0, m - s0, bs)
        if self.kv_quant:
            # the row's fp ring tail must cover its last R blocks from the
            # very first decode step — even on a fully-shared resident hit
            # the previous occupant's tail is stale for this request
            self.pool = self._tail_fn(self.pool, stage, jnp.int32(slot),
                                      jnp.int32(m))

        # ---- index the now-resident prompt prefix in L1 ---------------
        table_blocks = shared + fresh        # covers [0, m)
        for b in self.trie.register(ids, m, table_blocks):
            self.allocator.ref(b)            # the trie's own reference

        if temperature > 0.0:
            self._step_rng, sub = jax.random.split(self._step_rng)
            tok0 = sample_logits(logits, sub, temperature=temperature,
                                 top_k=top_k)
        else:
            tok0 = engine_mod.greedy(logits)

        self.stats["requests"] += 0 if resume else 1
        self.stats["hits"] += int(hit)
        self.stats["tokens_reused"] += depth
        self.stats["tokens_prefilled"] += m - depth
        self.stats["admissions"] += 1

        with self._wait("first_token"):
            first = int(tok0[0])
        st = _Slot(prompt, ids, m, max_new, use_recycling, admit,
                   stop_at_eos, depth, hit, mode, sim,
                   emitted=[first], t0=t0,
                   t_first=time.perf_counter(),
                   temperature=temperature, top_k=top_k, tenant=tenant)
        st.deadline_t = deadline_t
        if resume is not None:
            self._mark_resumed(st, resume)
            rec = m - depth
            st.tokens_recomputed += rec
            self.stats["preempted_tokens_recomputed"] += rec
        if (st.stop_at_eos and st.emitted[0] == EOS) or max_new == 1:
            # finished at its first token: the prompt prefix stays warm in
            # L1, but the row is never occupied
            result = self._result(st, stage=stage, cap=cap)
            for b in table_blocks:
                self.allocator.unref(b)
            return result

        row = np.full((self.nbt,), SENTINEL, np.int32)
        row[:len(table_blocks)] = table_blocks
        self._tables[slot] = row
        self._row_blocks[slot] = list(table_blocks)
        self._committed[slot] = need_later
        self._temp[slot] = temperature
        self._topk[slot] = top_k
        self.pool, self._tokens, self._pos = self._setrow_fn(
            self.pool, self._tokens, self._pos, slot, jnp.asarray(row),
            tok0, jnp.int32(m))
        self._slots[slot] = st
        return None

    # ------------------------------------------------------------------
    # chunked admission (the paged-native default)
    # ------------------------------------------------------------------
    def _admit_chunked(self, slot: int, prompt: str, ids, m: int,
                      max_new: int, use_recycling: bool, admit: bool,
                      stop_at_eos: bool, temperature: float, top_k: int,
                      t0: float, tenant: Optional[str] = None,
                      deadline_t: Optional[float] = None,
                      resume: Optional[dict] = None) -> None:
        """Queue ``prompt`` as a pending chunked admission on row
        ``slot``.  Only the admission *guarantee* runs here (can the pool
        ever provide this request's blocks without starving in-flight
        reservations? — conservatively assuming zero reuse, since the
        tier lookup is deferred to the first chunk step); all device work
        happens chunk-by-chunk inside ``decode_batch``.

        ``overcommit=True`` weakens the guarantee to the PROMPT's blocks
        only: the request's decode-time growth is not reserved up front,
        and when the pool later cannot cover a write the preemption
        machinery demotes a victim instead — how an undersized pool
        oversubscribes rather than rejecting.  Saturation that in-flight
        work will relieve raises ``PoolSaturated`` (scheduler keeps the
        request queued); ``AdmissionRejected`` is the permanent reject."""
        nb_total = _ceil_div(m + max_new, self.block)
        need = _ceil_div(m, self.block) if self.overcommit else nb_total
        owed = 0 if self.overcommit else sum(self._committed)
        avail = self.allocator.num_free() + self._evictable()
        if avail < need + owed:
            msg = (
                f"paged pool exhausted: request needs up to {need} "
                f"blocks, {avail - owed} obtainable "
                f"(free={self.allocator.num_free()}, "
                f"in-flight reservations={owed})")
            if self.active_slots() or self._pending:
                raise PoolSaturated(msg)
            raise AdmissionRejected(msg)
        self._committed[slot] = nb_total
        self._tables[slot] = SENTINEL
        self._row_blocks[slot] = []
        st = _Slot(prompt, ids, m, max_new, use_recycling, admit,
                   stop_at_eos, 0, False, "baseline", 0.0, emitted=[],
                   t0=t0, temperature=temperature, top_k=top_k,
                   tenant=tenant)
        st.deadline_t = deadline_t
        if resume is not None:
            self._mark_resumed(st, resume)
        self._pending[slot] = _PendingAdmission(st=st)
        return None

    def _begin_admission(self, slot: int, adm: _PendingAdmission) -> None:
        """First chunk step of a pending admission: tier lookup, shared-
        prefix composition (refcount++, zero copies), host promotion
        (block-granular direct upload — no staging cache), and fp ring
        seeding for int8 pools.  Running this lazily — at the first chunk,
        not at admit time — lets the lookup see blocks that admissions
        queued in the same scheduler step have already sealed."""
        st = adm.st
        bs = self.block
        ids, m = st.ids, st.m
        depth, hit, mode, sim = 0, False, "baseline", 0.0
        chain: List[Tuple[int, int]] = []
        res, host_cache = None, None
        if st.use_recycling:
            depth, hit, mode, sim, chain, res, host_cache = \
                self._lookup_tiers(st.prompt, ids, m)
        st.depth, st.hit, st.mode, st.sim = depth, hit, mode, sim
        aligned = (depth // bs) * bs

        # NB: only the HOST table mirror is updated during admission —
        # the device table row stays all-sentinel until the final chunk
        # installs it, so the batched decode (which writes through every
        # row's device table) can never scribble into a half-admitted
        # row's blocks at a stale position.  Chunk steps receive the host
        # mirror as an explicit operand instead.
        if mode == "resident_block":
            shared = [b for b, _ in chain[:aligned // bs]]
            for i, b in enumerate(shared):
                self.allocator.ref(b)
                self._tables[slot][i] = b
            self._row_blocks[slot] = list(shared)
            self._committed[slot] -= len(shared)
            if depth % bs:
                # divergent partial boundary block: the first chunk
                # REWRITES [aligned, depth) into a private block from the
                # prompt ids — the shared original is never gathered,
                # never mutated, and costs no staging pass (CoW by
                # recomputation)
                self.stats["cow_copies"] += 1
        elif hit and depth:
            # L2 promotion without the staging round-trip: the entry's
            # [0, depth) moves block-by-block into fresh private blocks —
            # sealed int8 blocks verbatim, everything else (fp entries,
            # residual tails, converted legacy layouts, the sub-block
            # remainder of the boundary block) through the fp upload that
            # quantizes exactly once.  The chunks then write only
            # [depth, m) (``w_floor``): uploaded positions keep the
            # staged-identical entry values instead of a recomputation.
            nb_up = _ceil_div(depth, bs)
            up = 0
            if self.kv_quant and res is not None and res.entry is not None:
                up = min(self._q8_blocks(res.entry.cache, depth), nb_up)
            try:
                fresh = self.allocator.alloc_many(nb_up)
            except BlockPoolExhausted:
                # free list alone can't cover the batch — fall back to
                # the per-block path, which evicts cold L1 chains and,
                # under pressure, preempts a victim; a partial grab is
                # rolled back before the failure propagates (the caller
                # then cancels this admission cleanly)
                fresh = []
                try:
                    for _ in range(nb_up):
                        fresh.append(self._alloc_pressure(
                            exclude_pending=(slot,)))
                except (BlockPoolExhausted, InjectedFault):
                    for b in fresh:
                        self.allocator.unref(b)
                    raise
            for j, b in enumerate(fresh):
                self._tables[slot][j] = b
            self._row_blocks[slot] = list(fresh)
            self._committed[slot] -= len(fresh)
            self.stats["h2d_copies"] += 1
            moved = 0
            for j in range(up):
                ent = self._q8_block(res.entry.cache, j)
                moved += sum(int(a.nbytes)
                             for s in ent.values() for a in s.values())
                self.pool = self._upload_q8_blk_fn(self.pool, ent,
                                                   jnp.int32(fresh[j]))
            self.stats["q8_block_promotions"] += up
            for j in range(up, nb_up):
                blk = self._host_block(host_cache, j)
                moved += sum(int(a.nbytes)
                             for s in blk.values() for a in s.values())
                self.pool = self._upload_blk_fn(self.pool, blk,
                                                jnp.int32(fresh[j]))
            self.stats["h2d_bytes"] += moved
            adm.w_floor = depth

        # int8 pools: the first chunk's queries read their last R blocks
        # of history from the row's fp ring tail; seed it like the staged
        # path's _fill_tail would have — exact fp from the entry's
        # residual on a host promotion (covering the uploaded partial
        # boundary block), dequantized pool content on a resident hit
        if self.kv_quant and mode == "resident_block" and aligned:
            self.pool = self._seedtail_fn(
                self.pool, jnp.int32(slot),
                jnp.asarray(self._tables[slot]), jnp.int32(aligned))
        elif self.kv_quant and hit and depth:
            self.pool = self._settail_fn(
                self.pool, jnp.int32(slot),
                self._host_ring_window(host_cache, depth))

        # semantic block-donor grafting: only on a prefix MISS (both
        # tiers), so the exact/partial paths are byte-identical to
        # semantic=False engines
        if self.semantic and st.use_recycling and not hit:
            plan = self.recycler.lookup_semantic(st.prompt, ids)
            if plan is not None:
                self._install_graft(slot, adm, plan)

        adm.next_c0 = aligned
        adm.started = True

    # ------------------------------------------------------------------
    # semantic block-donor grafting
    # ------------------------------------------------------------------
    def _install_graft(self, slot: int, adm: _PendingAdmission,
                       plan: GraftPlan) -> None:
        """Wire a nominated donor graft into the pending admission: put
        the donor's interior blocks [interior_lo, interior_hi) into the
        row's table (refcount++ when the donor chain is device-resident,
        block-granular fp promotion from the host entry otherwise) and
        split the prompt into recompute segments around them.  The graft
        is PROVISIONAL until ``_graft_gate`` accepts it after segment 1's
        boundary recompute."""
        st = adm.st
        bs = self.block
        e = plan.entry
        lo, hi = plan.interior_lo, plan.interior_hi
        # donor fp view: uploads on the host path + the gate's reference
        host = e.cache
        if kvq.is_quantized(host):
            host = kvq.dequantize_tree(host)
        if not self._host_layout_ok(host):
            host = self._convert_dense_quant(host)
            self.stats["layout_conversions"] += 1
        # resident fast path: the donor's own chain still holds the
        # interior on device — share it in place, zero copies.  peek, not
        # lookup: sizing up a donor must not stamp recency
        d_res, chain = self.trie.peek(e.token_ids[:e.length])
        if d_res >= plan.graft_end:
            blocks = [b for b, _ in chain[lo:hi]]
            for b in blocks:
                self.allocator.ref(b)
            self.stats["semantic_resident_grafts"] += 1
        else:
            blocks = []
            moved = 0
            try:
                for j in range(lo, hi):
                    b = self._alloc_block()
                    blk = self._host_block(host, j)
                    moved += sum(int(np.asarray(a).nbytes)
                                 for s in blk.values() for a in s.values())
                    self.pool = self._upload_blk_fn(self.pool, blk,
                                                    jnp.int32(b))
                    blocks.append(b)
            except (BlockPoolExhausted, InjectedFault):
                # a graft is opportunistic: under pressure, skip it and
                # recompute contiguously (token-identical to
                # semantic=False) instead of stealing blocks from
                # in-flight rows
                for b in blocks:
                    self.allocator.unref(b)
                return
            self.stats["h2d_copies"] += 1
            self.stats["h2d_bytes"] += moved
            self.stats["semantic_host_grafts"] += 1
        for k, b in enumerate(blocks):
            self._tables[slot][lo + k] = b
        self._row_blocks[slot] = [int(x) for x in self._tables[slot]
                                  if x != SENTINEL]
        self._committed[slot] -= len(blocks)
        # the gate's reference: the donor's OWN boundary blocks [b0, lo)
        # in fp — what the recompute would reproduce if the differing
        # head changed nothing
        ref = {}
        for seg, c in host.items():
            ref[seg] = {n: np.asarray(c[n][:, 0, plan.b0 * bs:lo * bs])
                        for n in ("k", "v")}
        adm.graft = plan
        adm.graft_ref = ref
        adm.segs = [(0, plan.seg1_end), (plan.graft_end, st.m)]
        adm.seg_i = 0
        adm.gate_at = plan.seg1_end
        adm.reg_cap = plan.seg1_end

    def _graft_gate(self, slot: int, adm: _PendingAdmission) -> None:
        """Fidelity gate, run when segment 1's chunks reach the graft.

        Measures how far the recomputed boundary block(s) [b0,
        interior_lo) — token-identical to the donor's but recomputed
        under the new prompt's real head — diverge from the donor's own
        K/V at those blocks (mean relative Frobenius error).  Boundary
        logits cannot see the un-attended interior under causal masking,
        so the boundary K/V delta is the observable proxy for how much
        the context difference would have perturbed the grafted region.
        Divergence <= ``graft_max_div`` accepts the graft (skip to the
        post-graft segment); otherwise every interior block is
        dereferenced and the admission falls back to a full contiguous
        recompute — token-identical to semantic=False."""
        plan = adm.graft
        st = adm.st
        bs = self.block
        lo, hi = plan.interior_lo, plan.interior_hi
        bids = [int(self._tables[slot][j]) for j in range(plan.b0, lo)]
        n = len(bids) * bs
        staged = self._stage_fn(self.pool, jnp.asarray(bids, jnp.int32),
                                n, n)
        with self._wait("graft_gate"):
            got = to_host(staged)
        rels = []
        for seg, ref in adm.graft_ref.items():
            for name in ("k", "v"):
                a = np.asarray(got[seg][name][:, 0, :n], np.float32)
                b = np.asarray(ref[name], np.float32)
                rels.append(np.linalg.norm(a - b)
                            / (np.linalg.norm(b) + 1e-9))
        div = float(np.mean(rels))
        self.semantic_gate_divs.append(div)
        if div <= self.graft_max_div:
            st.depth = plan.interior_tokens
            st.hit = True
            st.mode = "semantic_block"
            st.sim = plan.similarity
            self.stats["semantic_grafts"] += 1
            self.stats["tokens_grafted"] += plan.interior_tokens
            adm.seg_i = 1
            adm.next_c0 = plan.graft_end
            if self.kv_quant:
                # the post-graft chunk reads its last R history blocks
                # (the grafted interior) through the row's fp ring tail —
                # reseed it at the graft boundary like a resident hit
                self.pool = self._seedtail_fn(
                    self.pool, jnp.int32(slot),
                    jnp.asarray(self._tables[slot]),
                    jnp.int32(adm.next_c0))
        else:
            for j in range(lo, hi):
                b = int(self._tables[slot][j])
                self._tables[slot][j] = SENTINEL
                self.allocator.unref(b)
            self._row_blocks[slot] = [int(x) for x in self._tables[slot]
                                      if x != SENTINEL]
            self._committed[slot] += hi - lo
            self.stats["semantic_refusals"] += 1
            # a refused graft leaves NOTHING approximate in the row:
            # every remaining position recomputes contiguously, so the
            # registration cap is lifted and the prompt indexes like any
            # cold admission
            adm.graft = None
            adm.graft_ref = None
            adm.segs = None
            adm.seg_i = 0
            adm.gate_at = -1
            adm.reg_cap = 1 << 30

    def _admission_chunk(self, slot: int) -> None:
        """Advance one pending admission by ONE chunk: allocate the
        chunk's blocks (batched table update), run the single compiled
        chunk-prefill executable, extend the L1 registration frontier,
        and finish the admission when the chunk reaches the prompt end."""
        adm = self._pending[slot]
        st = adm.st
        bs = self.block
        if not adm.started:
            with span("engine.tier_lookup"):
                self._begin_admission(slot, adm)
        with span("engine.chunk"):
            c0 = adm.next_c0
            # a grafted admission chunks per SEGMENT: [0, seg1_end)
            # first, then — if the gate accepts — [graft_end, m),
            # skipping the grafted interior entirely
            seg_end = adm.segs[adm.seg_i][1] if adm.segs else st.m
            remaining = seg_end - c0
            C = next((s for s in self.chunk_shapes if s >= remaining),
                     self.prefill_chunk)
            n_valid = min(C, remaining)
            for idx in range(c0 // bs, (c0 + n_valid - 1) // bs + 1):
                if self._tables[slot][idx] == SENTINEL:
                    b = self._alloc_pressure(exclude_pending=(slot,))
                    self._tables[slot][idx] = b
                    self._committed[slot] -= 1
            # rebuild rather than append: a graft installs interior
            # blocks at HIGHER table indices than the segment being
            # chunked, and the row_blocks invariant is table order
            self._row_blocks[slot] = [int(x) for x in self._tables[slot]
                                      if x != SENTINEL]
            toks = np.zeros((1, C), np.int32)
            toks[0, :n_valid] = st.ids[c0:c0 + n_valid]
            logits, self.pool = self._chunk_fn(
                self.params, jnp.asarray(toks), self.pool,
                jnp.int32(slot), jnp.asarray(self._tables[slot]),
                jnp.int32(c0), jnp.int32(adm.w_floor), jnp.int32(n_valid))
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_dispatches"] += 1
            # progressive L1 registration: blocks this chunk sealed
            # become shareable immediately — a neighbor admitted this
            # same step can compose its table from them at ITS first
            # chunk.  ``reg_cap`` stops the frontier at the graft:
            # grafted interior and post-graft blocks are approximate
            # under THIS prompt's keys and must never serve an
            # exact-prefix lookup
            reg_len = min(c0 + n_valid, adm.reg_cap)
            if reg_len > 0:
                for b in self.trie.register(st.ids, reg_len,
                                            self._row_blocks[slot]):
                    self.allocator.ref(b)
            adm.next_c0 = c0 + n_valid
        if adm.segs and adm.seg_i == 0 and adm.next_c0 >= adm.gate_at:
            # segment 1 reached the graft: run the fidelity gate.  On
            # accept it advances next_c0 past the interior; on refusal it
            # clears the segments and the next chunk continues
            # contiguously from here — either way the admission resumes
            # at the next engine step
            self._graft_gate(slot, adm)
            return
        if adm.next_c0 >= st.m:
            self._finish_admission(slot, logits)

    def _admission_step_packed(self) -> None:
        """Advance EVERY pending admission by one chunk in ONE ragged
        packed dispatch — the ``prefill_mode="packed"`` replacement for
        the per-admission ``_admission_chunk`` loop.  Host-side per-
        segment work (tier lookup on first step, chunk sizing, block
        allocation, table-mirror updates) runs exactly as the chunked
        path does it; then all segments' tokens are packed back to back
        into one bucket-shaped buffer with per-segment descriptors
        (``pack_admission_segments``) and ``_packed_fn`` runs the whole
        step's prefill in a single executable.  Per-segment bookkeeping
        (progressive L1 registration, ``next_c0`` advance, admission
        finish off the segment's own last-valid logits) follows.

        Equivalence to the chunked path is exact: each segment's queries
        attend only its own history + chunk (segment-masked kernel), and
        distinct segments write disjoint pool blocks, so tokens are
        identical — only the dispatch count changes (1 per step vs 1 per
        pending admission)."""
        slots = sorted(self._pending)
        if not slots:
            return
        bs = self.block
        segs = []
        metas = []
        self._pending_planned = set()
        for slot in slots:
            if slot not in self._pending:
                continue          # cancelled by a neighbor's pressure
            adm = self._pending[slot]
            st = adm.st
            # planned slots' segment descriptors reference their blocks
            # until the packed dispatch lands — from here on this slot
            # must not be a preemption victim
            self._pending_planned.add(slot)
            try:
                if not adm.started:
                    with span("engine.tier_lookup"):
                        self._begin_admission(slot, adm)
                c0 = adm.next_c0
                remaining = st.m - c0
                C = next((s for s in self.chunk_shapes if s >= remaining),
                         self.prefill_chunk)
                n_valid = min(C, remaining)
                for idx in range(c0 // bs, (c0 + n_valid - 1) // bs + 1):
                    if self._tables[slot][idx] == SENTINEL:
                        b = self._alloc_pressure()
                        self._tables[slot][idx] = b
                        self._committed[slot] -= 1
            except (BlockPoolExhausted, InjectedFault):
                # contained: roll this admission back to a clean state
                # and requeue it; the other admissions' plans are intact
                self._pending_planned.discard(slot)
                if slot in self._pending:
                    stc = self._cancel_admission(slot)
                    payload = self._resume_payload(stc, [])
                    payload["slot"] = slot
                    self._events.append(("preempted", payload))
                    self.stats["preemptions"] += 1
                    self.stats["step_rollbacks"] += 1
                    self._preempted_now.add(slot)
                continue
            self._row_blocks[slot] = [int(x) for x in self._tables[slot]
                                      if x != SENTINEL]
            segs.append((slot, self._tables[slot].copy(), c0, adm.w_floor,
                         n_valid, C, st.ids[c0:c0 + n_valid]))
            metas.append((slot, adm, c0, n_valid))
        if not segs:
            self._pending_planned = set()
            return
        pk = pack_admission_segments(
            segs, block_size=bs, buckets=self.packed_buckets,
            max_segments=self.max_batch, table_width=self.nbt)
        logits, self.pool = self._packed_fn(
            self.params, jnp.asarray(pk["tokens"]), self.pool,
            jnp.asarray(pk["rows"]), jnp.asarray(pk["tables"]),
            jnp.asarray(pk["c0s"]), jnp.asarray(pk["w_floors"]),
            jnp.asarray(pk["valids"]), jnp.asarray(pk["q_offs"]),
            jnp.asarray(pk["seg_ids"]))
        self.stats["prefill_chunks"] += len(segs)
        self.stats["prefill_packed_steps"] += 1
        self.stats["prefill_dispatches"] += 1
        for i, (slot, adm, c0, n_valid) in enumerate(metas):
            st = adm.st
            reg_len = min(c0 + n_valid, adm.reg_cap)
            if reg_len > 0:
                for b in self.trie.register(st.ids, reg_len,
                                            self._row_blocks[slot]):
                    self.allocator.ref(b)
            adm.next_c0 = c0 + n_valid
            if adm.next_c0 >= st.m:
                self._finish_admission(slot, logits[i:i + 1])
        self._pending_planned = set()

    def _finish_admission(self, slot: int, logits) -> None:
        """Final chunk done: sample the first token, install the row's
        DEVICE block table (the first moment decode may write through it),
        arm the row for the batched decode loop, and account the
        admission."""
        with span("engine.first_token"):
            adm = self._pending.pop(slot)
            st = adm.st
            if st.temperature > 0.0:
                self._step_rng, sub = jax.random.split(self._step_rng)
                tok0 = sample_logits(logits, sub,
                                     temperature=st.temperature,
                                     top_k=st.top_k)
            else:
                tok0 = engine_mod.greedy(logits)
            with self._wait("first_token"):
                st.emitted = [int(tok0[0])]
            st.t_first = st.t_first or time.perf_counter()
            self.stats["requests"] += 0 if st.resume_emitted else 1
            if st.resume_emitted:
                rec = st.m - st.depth
                st.tokens_recomputed += rec
                self.stats["preempted_tokens_recomputed"] += rec
            self.stats["hits"] += int(st.hit)
            self.stats["tokens_reused"] += st.depth
            self.stats["tokens_prefilled"] += st.m - st.depth
            self.stats["admissions"] += 1
            self._temp[slot] = st.temperature
            self._topk[slot] = st.top_k
            self.pool, self._tokens, self._pos = self._setrow_fn(
                self.pool, self._tokens, self._pos, slot,
                jnp.asarray(self._tables[slot]), tok0, jnp.int32(st.m))
            self._slots[slot] = st

    # ------------------------------------------------------------------
    def _apply_table_updates(self,
                             updates: List[Tuple[int, int, int]]) -> None:
        """Apply (row, entry, block) table updates in ONE fixed-width
        dispatch; padding entries point past the table and are dropped."""
        W = self.nbt + 2 * self.max_batch
        assert len(updates) <= W, (len(updates), W)
        rows = np.zeros((W,), np.int32)
        idxs = np.full((W,), self.nbt, np.int32)
        blks = np.zeros((W,), np.int32)
        for j, (r, i, b) in enumerate(updates):
            rows[j], idxs[j], blks[j] = r, i, b
        self.pool = self._setents_batch_fn(
            self.pool, jnp.asarray(rows), jnp.asarray(idxs),
            jnp.asarray(blks))

    def _host_block(self, cache, j: int):
        """Block ``j`` of a promotable host entry (staging layout), as the
        per-block fp upload payload {seg: {k, v: (L, bs, H, D)}} —
        zero-padded when the entry's capacity axis ends mid-block (the
        pad positions sit beyond the promoted depth and are never
        attended)."""
        bs = self.block
        dt = jnp.dtype(self.cfg.dtype)
        out = {}
        for seg, c in cache.items():
            sub = {}
            for name in ("k", "v"):
                a = np.asarray(c[name][:, 0, j * bs:(j + 1) * bs])
                if a.shape[1] < bs:
                    pad = [(0, 0)] * a.ndim
                    pad[1] = (0, bs - a.shape[1])
                    a = np.pad(a, pad)
                sub[name] = jnp.asarray(a.astype(dt))
            out[seg] = sub
        return out

    def _q8_block(self, raw, j: int):
        """Block ``j`` of a quantized host entry's sealed int8 region, in
        the verbatim per-block upload layout (codes + scales, keepdim
        dropped).  Pure slicing — no arithmetic touches the stored bits."""
        bs = self.block
        ent = {}
        for seg, c in raw.items():
            sub = {}
            for name in ("k", "v"):
                leaf = c[name]
                ax = int(np.asarray(leaf["ax"]))
                sl = [slice(None)] * leaf[kvq._QKEY].ndim
                sl[ax] = slice(j * bs, (j + 1) * bs)
                sub[name] = jnp.asarray(leaf[kvq._QKEY][tuple(sl)][:, 0])
                sub[name + "_scale"] = jnp.asarray(
                    np.asarray(leaf["scale"])[tuple(sl)][:, 0, ..., 0])
            ent[seg] = sub
        return ent

    def _host_ring_window(self, cache, depth: int):
        """fp ring-tail payload for a host promotion: ring slot r holds
        the entry's (exact, residual-covered) values of the unique block
        ti in the last-R-blocks window of the promoted region [0, depth)
        with ti % R == r — computed host-side so only R blocks cross to
        the device.  Positions >= depth are zeroed; the chunk's own
        dual-writes fill them as the fresh suffix seals."""
        bs = self.block
        R = self.fp_tail_blocks
        lb = (depth - 1) // bs                 # last promoted block
        dt = jnp.dtype(self.cfg.dtype)
        r = np.arange(R)
        ti = lb - ((lb - r) % R)
        posm = ti[:, None] * bs + np.arange(bs)[None]          # (R, bs)
        out = {}
        for seg, c in cache.items():
            cap = np.asarray(c["k"]).shape[2]
            valid = ((posm >= 0) & (posm < min(depth, cap))).reshape(-1)
            idx = np.clip(posm, 0, cap - 1).reshape(-1)
            sub = {}
            for name in ("k", "v"):
                a = np.asarray(c[name])[:, 0, idx].astype(np.float32)
                a = a * valid[None, :, None, None]
                sub[name] = jnp.asarray(a.astype(dt))
            out[seg] = sub
        return out

    # ------------------------------------------------------------------
    def decode_batch(self) -> List[Tuple[int, GenResult]]:
        """One engine step: advance every pending chunked admission by ONE
        chunk, then one masked decode step over the paged pool (single
        dispatch) for the armed rows — a long admission never stalls the
        in-flight batch, it shares the step cadence with it.

        Before decoding, rows whose next write position crosses into an
        unallocated table entry get a fresh private block (on demand —
        device bytes track actual lengths, not capacity), and rows within
        ``prealloc_watermark`` positions of their block boundary have the
        NEXT block speculatively reserved, so table updates arrive in one
        batched dispatch instead of firing per row per boundary."""
        if self.fault_plan is not None:
            self.fault_plan.maybe_fire("replica_step", "injected: step fault")
        self._preempted_now = set()
        if self.prefill_mode == "packed":
            # ALL pending admissions advance in ONE ragged packed dispatch
            with span("engine.packed_admission"):
                self._admission_step_packed()
        else:
            for slot in sorted(self._pending):
                if slot not in self._pending:
                    continue      # cancelled by a neighbor's pressure
                with span("engine.admission", row=slot):
                    try:
                        self._admission_chunk(slot)
                    except (BlockPoolExhausted, InjectedFault):
                        # contained: roll this admission back and
                        # requeue it
                        if slot in self._pending:
                            stc = self._cancel_admission(slot)
                            payload = self._resume_payload(stc, [])
                            payload["slot"] = slot
                            self._events.append(("preempted", payload))
                            self.stats["preemptions"] += 1
                            self.stats["step_rollbacks"] += 1
                            self._preempted_now.add(slot)
        done: List[Tuple[int, GenResult]] = []
        with span("engine.emit"):
            for i in self.active_slots():
                st = self._slots[i]
                # a row whose admission just completed may already be
                # done (EOS at its first token, or a 1-token budget)
                if len(st.emitted) == 1 and (
                        (st.stop_at_eos and st.emitted[0] == EOS)
                        or st.max_new == 1):
                    done.append((i, self._result(st, row=i)))
                    self._release_row(i)
        active = self.active_slots()
        if not active:
            return done
        spec_faultable = (self.fault_plan is not None
                          and "alloc" in self.fault_plan.sites)
        if self.speculative and not spec_faultable:
            if self._spec_ready(active):
                with span("engine.spec_round"):
                    done.extend(self._spec_round(active))
                return done
            # sampled rows in the batch, bundle past capacity, or blocks
            # unobtainable: fall back to the plain step for this round
            self.stats["spec_fallback_steps"] += 1
        with span("engine.table_update"):
            active = self._grow_tables(active)
        if not active:
            return done

        bs, nbt = self.block, self.nbt
        self.stats["decode_pages_walked"] += sum(
            min((self._slots[i].m + len(self._slots[i].emitted) - 1) // bs,
                nbt - 1) + 1 for i in active)
        self.stats["decode_table_pages"] += len(active) * nbt
        with span("engine.decode"):
            if np.any(self._temp > 0.0):
                self._step_rng, sub = jax.random.split(self._step_rng)
                self.stats["sampled_steps"] += 1
                nxt, self._tokens, self.pool, self._pos = \
                    self._pstep_sampled_fn(
                        self.params, self._tokens, self.pool, self._pos,
                        jnp.asarray(self._temp), jnp.asarray(self._topk),
                        sub, max(int(self._topk.max()), 1))
            else:
                nxt, self._tokens, self.pool, self._pos = self._pstep_fn(
                    self.params, self._tokens, self.pool, self._pos)
            with self._wait("decode"):
                toks = np.asarray(nxt)
        self.stats["batched_decode_steps"] += 1
        with span("engine.emit"):
            for i in active:
                st = self._slots[i]
                st.emitted.append(int(toks[i]))
                if ((st.stop_at_eos and st.emitted[-1] == EOS)
                        or len(st.emitted) >= st.max_new):
                    done.append((i, self._result(st, row=i)))
                    self._release_row(i)
        return done

    def _grow_tables(self, active: List[int]) -> List[int]:
        """Give every row whose next write crosses into an unallocated
        table entry a fresh block (preempting under pressure), reserve
        the next block of rows near their boundary, and apply all the
        table updates in one dispatch.  Returns the rows still active."""
        bs = self.block
        updates: List[Tuple[int, int, int]] = []
        for i in active:
            if i in self._preempted_now:
                continue          # victim of an earlier row's pressure
            st = self._slots[i]
            p = st.m + len(st.emitted) - 1   # position this step writes
            idx = p // bs
            if self._tables[i, idx] == SENTINEL:
                try:
                    b = self._alloc_pressure(exclude_rows=(i,))
                except (BlockPoolExhausted, InjectedFault):
                    # no victim left but this row itself: it yields its
                    # slot and comes back through the resume path
                    self._self_preempt(i)
                    continue
                self._tables[i, idx] = b
                self._row_blocks[i].append(b)
                self._committed[i] -= 1
                updates.append((i, idx, b))
            if (self.prealloc_watermark and idx + 1 < self.nbt
                    and p % bs >= bs - self.prealloc_watermark
                    and (idx + 1) * bs < st.m + st.max_new
                    and self._tables[i, idx + 1] == SENTINEL):
                try:
                    b = self._alloc_block()
                except (BlockPoolExhausted, InjectedFault):
                    # the watermark reservation is an optimisation, not a
                    # requirement — under pressure it just doesn't happen
                    continue
                self._tables[i, idx + 1] = b
                self._row_blocks[i].append(b)
                self._committed[i] -= 1
                updates.append((i, idx + 1, b))
                self.stats["spec_preallocs"] += 1
        if self._preempted_now:
            updates = [(r, i, b) for (r, i, b) in updates
                       if r not in self._preempted_now]
            active = [i for i in active if i not in self._preempted_now]
        if updates:
            self._apply_table_updates(updates)
        return active

    # ------------------------------------------------------------------
    def _release_row(self, row: int) -> None:
        """Free the row: drop its table references (prefix blocks indexed
        in L1 survive as the device cache tier; generation-only blocks
        fall to refcount 0 and return to the free list)."""
        for b in self._row_blocks[row]:
            self.allocator.unref(b)
        self._row_blocks[row] = []
        self._committed[row] = 0
        self._tables[row] = SENTINEL
        self._temp[row] = 0.0
        self._topk[row] = 0
        self.pool = self._clear_fn(self.pool, row)
        self._slots[row] = None

    # ------------------------------------------------------------------
    def _result(self, st: _Slot, *, row: Optional[int] = None, stage=None,
                cap: Optional[int] = None) -> GenResult:
        # contamination rule: a grafted row's K/V is approximate for THIS
        # prompt's tokens (interior from a different context, suffix
        # computed attending it) — admitting it to the host store would
        # let future exact-prefix lookups serve approximate caches
        if st.admit and st.mode != "semantic_block":
            cap = cap or self._capacity(st.m + st.max_new)
            if stage is None:
                # harvest from the pool: gather the row's prompt blocks
                # back into the host-store layout, valid [0, m) — int8
                # pools keep their codes verbatim (_harvest)
                ids = [b for b in self._tables[row]
                       if b != SENTINEL][:_ceil_div(st.m, self.block)]
                host = self._harvest(jnp.asarray(ids, jnp.int32),
                                     st.m, cap)
            else:
                # instant finish: the staging cache already holds exactly
                # [0, m) — generated positions were never written into it
                host = to_host(stage)
            self.recycler.admit(st.prompt, st.ids, host, st.m, cap,
                                tenant=st.tenant)
        # a resumed slot's "prompt" includes the tokens emitted before the
        # preemption; stitch them back so the result describes the whole
        # request, not just its last residency
        gen = st.resume_emitted + st.emitted
        all_ids = np.concatenate([st.ids, np.asarray(st.emitted, np.int32)])
        return GenResult(
            text=self.tok.decode(gen),
            token_ids=all_ids,
            latency_s=time.perf_counter() - st.t0,
            prompt_tokens=st.m - len(st.resume_emitted),
            gen_tokens=len(gen),
            reuse_depth=st.depth,
            cache_hit=st.hit,
            mode=st.mode if st.use_recycling else "baseline",
            prompt_similarity=st.sim,
            ttft_s=max(st.t_first - st.t0, 0.0),
            preemptions=st.preemptions,
            tokens_recomputed=st.tokens_recomputed,
        )

    # ------------------------------------------------------------------
    def check_invariants(self) -> None:
        """Paged-pool global invariants (fuzzed in tests):
          * allocator free/live accounting is consistent
          * every block's refcount equals (#tables naming it) + (1 if the
            L1 trie indexes it) — so a block in two tables is provably
            shared, and no freed block is reachable
          * table entries beyond a row's blocks are sentinel
          * an armed (decoding) row's table names a CONTIGUOUS prefix
            whose frontier never runs past the row's write position by
            more than the one-block watermark prealloc — after a
            speculative round this is exactly the post-rollback bound
            (the accept frontier's block, plus at most the prealloc)"""
        self.allocator.check()
        expected: Dict[int, int] = {}
        for i in range(self.max_batch):
            for b in self._row_blocks[i]:
                expected[b] = expected.get(b, 0) + 1
            named = [b for b in self._tables[i] if b != SENTINEL]
            assert named == self._row_blocks[i], \
                (i, named, self._row_blocks[i])
        for b in self.trie.blocks():
            expected[b] = expected.get(b, 0) + 1
        for b in range(1, self.allocator.num_blocks):
            assert self.allocator.refcount(b) == expected.get(b, 0), \
                (b, self.allocator.refcount(b), expected.get(b, 0))
        for i in range(self.max_batch):
            st = self._slots[i]
            if st is None:
                continue    # pending admissions may hold grafted
                            # interior blocks at non-contiguous indices
            named_idx = [j for j in range(self.nbt)
                         if self._tables[i, j] != SENTINEL]
            assert named_idx == list(range(len(named_idx))), \
                (i, named_idx)
            if named_idx:
                p = st.m + len(st.emitted) - 1
                assert named_idx[-1] <= p // self.block + 1, \
                    (i, named_idx[-1], p, self.block)
