"""Serving engine: tokenizer -> recycler -> prefill(suffix) -> decode loop.

This is the paper's evaluation loop (§4.4) as a production surface:

  baseline run    engine.generate(p, use_recycling=False)
  cache build     engine.precache(prompts)          # §4.4 "Cache Construction"
  recycled run    engine.generate(p)                # retrieval + prefix test
                                                    # + past_key_values reuse

plus what the paper doesn't have: capacity-bucketed cache allocation (stable
jit signatures), automatic admission of finished generations (multi-turn
prefix reuse), block-radix partial hits, and byte-budget LRU eviction.

Latency accounting mirrors §4.5: wall time around the whole generate call
with ``block_until_ready`` as the synchronize analogue, reuse depth k, and
prompt similarity from the retrieval stage.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import HashEmbedder, Recycler
from repro.core import quant
from repro.core.blockpool import AdmissionRejected
from repro.core.kvstore import to_host
from repro.core.recycler import (grow_capacity, is_trimmable,
                                 shrink_capacity, trim_to_depth)
from repro.data.tokenizer import ByteTokenizer, EOS
from repro.models import decode_step, init_cache, prefill
from repro.runtime import Runtime, LOCAL
from repro.serving.sampling import greedy, sample_batched, sample_logits


@dataclass
class GenResult:
    text: str
    token_ids: np.ndarray
    latency_s: float
    prompt_tokens: int
    gen_tokens: int
    reuse_depth: int = 0
    cache_hit: bool = False
    mode: str = "baseline"
    prompt_similarity: float = 0.0
    # admission latency: seconds from admission start to the FIRST sampled
    # token (the paper's latency metric isolates prefill cost; this is its
    # per-request serving analogue, what the chunked-admission path exists
    # to shrink).  0.0 when the engine predates the measurement.
    ttft_s: float = 0.0
    # pressure-safe serving (paged engine): how often this request was
    # preempted + resumed, and how many positions its resumes had to
    # re-prefill — 0/0 on engines without preemption
    preemptions: int = 0
    tokens_recomputed: int = 0


class Engine:
    def __init__(self, cfg: ModelConfig, params, *,
                 tokenizer: Optional[ByteTokenizer] = None,
                 recycler: Optional[Recycler] = None,
                 enable_partial: bool = False,
                 block_size: int = 64,
                 max_new_tokens: int = 32,
                 window: int = 0,
                 compress_host_cache: bool = False,
                 compress_residual: Optional[int] = None,
                 kv_quant: bool = False,
                 semantic: bool = False,
                 graft_min_agree: float = 1.0,
                 graft_boundary_blocks: int = 1,
                 sample_seed: int = 0,
                 rt: Runtime = LOCAL):
        self.cfg = cfg
        self.params = params
        self._sample_key = jax.random.PRNGKey(sample_seed)
        self.tok = tokenizer or ByteTokenizer(cfg.vocab_size)
        if compress_residual is None:
            compress_residual = quant.DEFAULT_RESIDUAL
        self.recycler = recycler or Recycler(
            embedder=HashEmbedder(), enable_partial=enable_partial,
            block_size=block_size, compress=compress_host_cache,
            compress_residual=compress_residual, semantic=semantic,
            graft_min_agree=graft_min_agree,
            graft_boundary_blocks=graft_boundary_blocks)
        self.block = block_size
        self.max_new = max_new_tokens
        self.window = window
        self.kv_quant = kv_quant
        self.rt = rt
        self._prefill_fn = jax.jit(
            lambda p, t, c, sp: prefill(cfg, p, t, c, start_pos=sp,
                                        window=window, rt=rt))
        self._decode_fn = jax.jit(
            lambda p, t, c, pos: decode_step(cfg, p, t, c, pos,
                                             window=window, rt=rt))
        self.stats = {"requests": 0, "hits": 0, "tokens_reused": 0,
                      "tokens_prefilled": 0}

    # ------------------------------------------------------------------
    def _capacity(self, n: int) -> int:
        return ((n + self.block - 1) // self.block) * self.block

    def _make_cache(self, capacity: int):
        return init_cache(self.cfg, 1, capacity, window=self.window,
                          dtype=jnp.dtype(self.cfg.dtype),
                          kv_quant=self.kv_quant)

    # ------------------------------------------------------------------
    def precache(self, prompts, lengths: Optional[Dict[str, int]] = None):
        """Paper §4.4 cache construction: one forward pass per cache prompt
        with caching enabled; serialize to host and index by embedding."""
        for p in prompts:
            ids = self.tok.encode(p)
            cap = self._capacity(len(ids) + self.max_new)
            cache = self._make_cache(cap)
            _, cache = self._prefill_fn(self.params, jnp.asarray(ids)[None],
                                        cache, 0)
            self.recycler.admit(p, ids, to_host(cache), len(ids), cap)

    # ------------------------------------------------------------------
    def generate(self, prompt: str, *, max_new_tokens: Optional[int] = None,
                 use_recycling: bool = True, admit: bool = False,
                 stop_at_eos: bool = True, temperature: float = 0.0,
                 top_k: int = 0,
                 tenant: Optional[str] = None) -> GenResult:
        max_new = max_new_tokens or self.max_new
        t0 = time.perf_counter()
        ids = self.tok.encode(prompt)
        m = len(ids)
        cap = self._capacity(m + max_new)
        # greedy (the paper's do_sample=False) unless this request opted in;
        # the key is folded per request AND per position -> deterministic
        # replays without coupling requests to each other
        if temperature > 0.0:
            req_key = jax.random.fold_in(self._sample_key,
                                         self.stats["requests"])
            pick = lambda lg, p: sample_logits(
                lg, jax.random.fold_in(req_key, p),
                temperature=temperature, top_k=top_k)
        else:
            pick = lambda lg, p: greedy(lg)

        depth, hit, mode, sim = 0, False, "baseline", 0.0
        if use_recycling:
            res = self.recycler.lookup(prompt, ids)
            sim = res.similarity
            if res.hit:
                depth, hit, mode = res.reuse_depth, True, res.mode
                host_cache = grow_capacity(res.cache, cap)
                cache = jax.tree.map(jnp.asarray, host_cache)
            else:
                mode = "miss"
        if not hit:
            cache = self._make_cache(cap)

        suffix = jnp.asarray(ids[depth:])[None]
        logits, cache = self._prefill_fn(self.params, suffix,
                                         cache, depth)
        out_ids = []
        tok = pick(logits, m)[:, None]
        jax.block_until_ready(tok)
        ttft = time.perf_counter() - t0
        pos = m
        for _ in range(max_new):
            out_ids.append(int(tok[0, 0]))
            if stop_at_eos and out_ids[-1] == EOS:
                break
            logits, cache = self._decode_fn(self.params, tok, cache,
                                            jnp.int32(pos))
            tok = pick(logits, pos + 1)[:, None]
            pos += 1
        jax.block_until_ready(logits)
        latency = time.perf_counter() - t0

        all_ids = np.concatenate([ids, np.asarray(out_ids, np.int32)])
        if admit:
            host = to_host(cache)
            if is_trimmable(host):
                # admit at PROMPT depth: future prompts extending this one
                # (without the generated reply) still pass the exact-prefix
                # test; generated positions are masked out.
                self.recycler.admit(prompt, ids, trim_to_depth(host, m),
                                    m, cap, tenant=tenant)
            else:
                # recurrent state can't rewind: admit the full trajectory
                self.recycler.admit(prompt, all_ids, host, len(all_ids), cap,
                                    tenant=tenant)

        self.stats["requests"] += 1
        self.stats["hits"] += int(hit)
        self.stats["tokens_reused"] += depth
        self.stats["tokens_prefilled"] += m - depth
        return GenResult(
            text=self.tok.decode(out_ids),
            token_ids=all_ids,
            latency_s=latency,
            prompt_tokens=m,
            gen_tokens=len(out_ids),
            reuse_depth=depth,
            cache_hit=hit,
            mode=mode if use_recycling else "baseline",
            prompt_similarity=sim,
            ttft_s=ttft,
        )

    # ------------------------------------------------------------------
    def warmup(self, prompt: str, *, max_new_tokens: Optional[int] = None,
               use_recycling: bool = True) -> None:
        """Compile the shapes a subsequent timed call will use (the paper's
        T4 runs have no compile step; jit does — exclude it from latency)."""
        self.generate(prompt, max_new_tokens=max_new_tokens,
                      use_recycling=use_recycling, admit=False)


# ---------------------------------------------------------------------------
# continuous batching: slot-based KV pool
# ---------------------------------------------------------------------------
@dataclass
class _Slot:
    """Host-side record for one in-flight request occupying a pool row."""
    prompt: str
    ids: np.ndarray              # prompt token ids
    m: int                       # prompt length
    max_new: int
    use_recycling: bool
    admit: bool
    stop_at_eos: bool
    depth: int
    hit: bool
    mode: str
    sim: float
    emitted: list = field(default_factory=list)
    t0: float = 0.0
    t_first: float = 0.0         # when the first token was sampled (TTFT)
    temperature: float = 0.0     # 0 = greedy (the paper's do_sample=False)
    top_k: int = 0
    tenant: Optional[str] = None  # labels admitted host entries (quotas)
    # preemption bookkeeping (paged engine): tokens already emitted before
    # this slot was demoted + resumed, re-derived through warm admission.
    # ``resume_emitted`` is prepended to the row's fresh output, and the
    # per-request counters below survive across preempt/resume cycles so
    # observability sees the whole request, not just its last residency.
    resume_emitted: list = field(default_factory=list)
    preemptions: int = 0
    tokens_recomputed: int = 0
    deadline_t: Optional[float] = None  # absolute deadline (victim tiebreak)


def _pool_load_row(pool, row, slot, tokens, pos, tok0, m):
    """Scatter a single-request cache (standard layout: slot_pos (L, C),
    k/v (L, 1, C, ...)) into pool row ``slot`` and prime its token/pos."""
    def walk(pl, rw, name=None):
        if isinstance(pl, dict):
            return {k: walk(pl[k], rw[k], k) for k in pl}
        if name == "slot_pos":
            return pl.at[:, slot].set(rw)
        return pl.at[:, slot].set(rw[:, 0])
    return (walk(pool, row), tokens.at[slot].set(tok0),
            pos.at[slot].set(m))


def _pool_read_row(pool, slot):
    """Gather pool row ``slot`` back into the single-request cache layout
    (what the recycler stores and ``prefill`` consumes)."""
    def walk(pl, name=None):
        if isinstance(pl, dict):
            return {k: walk(pl[k], k) for k in pl}
        if name == "slot_pos":
            return pl[:, slot]
        return pl[:, slot][:, None]
    return walk(pool)


def _donor_width(cache) -> int:
    """Widest attention slot axis in a host cache pytree (0 if none) —
    what has to fit a pool row, since buffers can grow but never shrink."""
    w = 0
    def walk(t, name=None):
        nonlocal w
        if isinstance(t, dict):
            for k, v in t.items():
                walk(v, k)
        elif name == "slot_pos":
            w = max(w, t.shape[-1])
    walk(cache)
    return w


class BatchedEngine(Engine):
    """Continuous-batching engine over a slot-based KV pool.

    The pool is one per-slot cache pytree ``init_cache(cfg, max_batch,
    capacity, per_slot=True)``: row b holds request b's KVs with its own
    ``slot_pos`` row, so one jitted ``decode_step`` call advances every
    in-flight request by a token regardless of their (different) depths.

    Admission is a single-row prefill — exactly the serial engine's path,
    including the recycler lookup, so a batch freely mixes exact-prefix
    hits, partial-block hits, and cold misses — followed by one scatter of
    that row into the pool.  Finished rows (EOS or token budget) are freed
    at the step boundary; the scheduler refills them from its queue, which
    is what makes the batch *continuous* rather than lockstep.

    Invariants (tested in tests/test_slot_pool.py):
      * rows never attend across slots — masking is per-row ``slot_pos``;
      * a row's decoded tokens are identical to a serial ``generate`` of
        the same request (greedy; tests/test_scheduler_batching.py);
      * freed rows need no scrubbing — admission overwrites the whole row,
        and stale slots stay masked because slot_pos is overwritten too.

    Trunk-attention architectures only (GQA/MHA; no MLA, recurrent state,
    or enc-dec rows — those can't be sliced per slot).
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 capacity: int = 256, **kw):
        super().__init__(cfg, params, **kw)
        self.max_batch = max_batch
        self.capacity = capacity
        # actual slot-axis width of a pool row (ring width when windowed)
        self._eff_cap = min(self.window, capacity) if self.window else capacity
        # validates the arch supports per-slot pooling (raises otherwise)
        self.pool = init_cache(cfg, max_batch, capacity, window=self.window,
                               dtype=jnp.dtype(cfg.dtype),
                               kv_quant=self.kv_quant, per_slot=True)
        self._tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self._pos = jnp.zeros((max_batch,), jnp.int32)
        self._slots: List[Optional[_Slot]] = [None] * max_batch
        # per-row sampling controls (0 temperature = greedy row); kept on
        # host so the all-greedy fast path costs no rng or sort work
        self._temp = np.zeros((max_batch,), np.float32)
        self._topk = np.zeros((max_batch,), np.int32)
        self._step_rng = self._sample_key
        # donate pool/tokens/pos: the step rewrites a handful of slots, so
        # without donation every decode step memcpys the whole pool
        self._load_fn = jax.jit(_pool_load_row, donate_argnums=(0, 3, 4))
        self._read_fn = jax.jit(_pool_read_row)
        self._bstep_fn = jax.jit(self._batched_step, donate_argnums=(1, 2, 3))
        self._bstep_sampled_fn = jax.jit(self._batched_step_sampled,
                                         donate_argnums=(1, 2, 3),
                                         static_argnums=(7,))
        self.stats.update({"batched_decode_steps": 0, "oversize_skips": 0,
                           "admissions": 0, "sampled_steps": 0})

    def _batched_step(self, params, tokens, pool, pos):
        # greedy is looked up at trace time on purpose: tests substitute it
        # to force early EOS in both the serial and batched paths
        logits, pool = decode_step(self.cfg, params, tokens, pool, pos,
                                   window=self.window, rt=self.rt)
        nxt = greedy(logits)                      # (B,)
        return nxt, nxt[:, None], pool, pos + 1

    def _batched_step_sampled(self, params, tokens, pool, pos, temp, topk,
                              rng, topk_cap):
        """Mixed-policy step: rows with temperature > 0 draw from their
        per-row categorical (per-row dynamic top-k), rows at 0 stay
        greedy — one dispatch either way (ROADMAP open item).
        ``topk_cap`` (static) is the batch's max requested k, so no row's
        distribution is silently narrowed by a fixed cap."""
        logits, pool = decode_step(self.cfg, params, tokens, pool, pos,
                                   window=self.window, rt=self.rt)
        nxt = sample_batched(logits, rng, temperature=temp, top_k=topk,
                             top_k_cap=topk_cap)
        return nxt, nxt[:, None], pool, pos + 1

    def _advance(self):
        """One decode step over the pool, dispatching the greedy or the
        sampled executable depending on whether any row samples."""
        if np.any(self._temp > 0.0):
            self._step_rng, sub = jax.random.split(self._step_rng)
            self.stats["sampled_steps"] += 1
            return self._bstep_sampled_fn(
                self.params, self._tokens, self.pool, self._pos,
                jnp.asarray(self._temp), jnp.asarray(self._topk), sub,
                max(int(self._topk.max()), 1))
        return self._bstep_fn(self.params, self._tokens, self.pool,
                              self._pos)

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    # ------------------------------------------------------------------
    def admit_slot(self, slot: int, prompt: str, *,
                   max_new_tokens: Optional[int] = None,
                   use_recycling: bool = True, admit: bool = False,
                   stop_at_eos: bool = True, temperature: float = 0.0,
                   top_k: int = 0,
                   tenant: Optional[str] = None) -> Optional[GenResult]:
        """Prefill ``prompt`` into pool row ``slot`` (recycled prefix when
        available).  Returns a GenResult immediately — leaving the slot
        free — iff the request finishes at its very first token."""
        if self._slots[slot] is not None:
            raise ValueError(f"slot {slot} is occupied")
        max_new = max_new_tokens or self.max_new
        t0 = time.perf_counter()
        ids = self.tok.encode(prompt)
        m = len(ids)
        if m + max_new > self.capacity:
            raise AdmissionRejected(
                f"request needs {m + max_new} positions; pool "
                f"capacity is {self.capacity}")

        depth, hit, mode, sim = 0, False, "baseline", 0.0
        if use_recycling:
            res = self.recycler.lookup(prompt, ids)
            sim = res.similarity
            if res.hit and _donor_width(res.cache) > self._eff_cap:
                # cached buffers can't shrink into a pool row; honest miss
                self.stats["oversize_skips"] += 1
                mode = "miss"
            elif res.hit:
                depth, hit, mode = res.reuse_depth, True, res.mode
                cache = jax.tree.map(
                    jnp.asarray, grow_capacity(res.cache, self._eff_cap))
            else:
                mode = "miss"
        if not hit:
            cache = self._make_cache(self.capacity)

        suffix = jnp.asarray(ids[depth:])[None]
        logits, cache = self._prefill_fn(self.params, suffix, cache, depth)
        if temperature > 0.0:
            self._step_rng, sub = jax.random.split(self._step_rng)
            tok0 = sample_logits(logits, sub, temperature=temperature,
                                 top_k=top_k)
        else:
            tok0 = greedy(logits)                 # (1,)

        self.stats["requests"] += 1
        self.stats["hits"] += int(hit)
        self.stats["tokens_reused"] += depth
        self.stats["tokens_prefilled"] += m - depth
        self.stats["admissions"] += 1

        st = _Slot(prompt, ids, m, max_new, use_recycling, admit,
                   stop_at_eos, depth, hit, mode, sim,
                   emitted=[int(tok0[0])], t0=t0,
                   t_first=time.perf_counter(),
                   temperature=temperature, top_k=top_k, tenant=tenant)
        if (st.stop_at_eos and st.emitted[0] == EOS) or max_new == 1:
            # finished at the first token: never occupies the pool
            return self._result(st, host_cache=lambda: to_host(cache))
        self.pool, self._tokens, self._pos = self._load_fn(
            self.pool, cache, jnp.int32(slot), self._tokens, self._pos,
            tok0, jnp.int32(m))
        self._slots[slot] = st
        self._temp[slot] = temperature
        self._topk[slot] = top_k
        return None

    # ------------------------------------------------------------------
    def decode_batch(self) -> List[Tuple[int, GenResult]]:
        """One masked decode step over the whole pool (single jit dispatch).
        Appends each active row's next token; returns the (slot, result)
        pairs of rows that finished — their slots are freed for the
        scheduler to refill before the next step."""
        active = self.active_slots()
        if not active:
            return []
        nxt, self._tokens, self.pool, self._pos = self._advance()
        toks = np.asarray(nxt)
        self.stats["batched_decode_steps"] += 1
        done: List[Tuple[int, GenResult]] = []
        for i in active:
            st = self._slots[i]
            st.emitted.append(int(toks[i]))
            if ((st.stop_at_eos and st.emitted[-1] == EOS)
                    or len(st.emitted) >= st.max_new):
                done.append((i, self._result(
                    st, host_cache=lambda i=i: to_host(
                        self._read_fn(self.pool, jnp.int32(i))))))
                self._slots[i] = None
                self._temp[i] = 0.0
                self._topk[i] = 0
        return done

    # ------------------------------------------------------------------
    def _result(self, st: _Slot, host_cache) -> GenResult:
        all_ids = np.concatenate([st.ids, np.asarray(st.emitted, np.int32)])
        if st.admit:
            host = trim_to_depth(host_cache(), st.m)
            # per-slot pools exist only for trunk attention, so the row is
            # always trimmable: admit at prompt depth like the serial path.
            # Shrink the row back to the serial path's bucketed width so the
            # host store doesn't pay pool-capacity bytes per entry (safe:
            # unwrapped slots hold slot == position; ring rows can't shrink)
            cap = self._capacity(st.m + st.max_new)
            if not self.window and cap < self._eff_cap:
                host = shrink_capacity(host, cap)
            else:
                cap = self.capacity
            self.recycler.admit(st.prompt, st.ids, host, st.m, cap,
                                tenant=st.tenant)
        return GenResult(
            text=self.tok.decode(st.emitted),
            token_ids=all_ids,
            latency_s=time.perf_counter() - st.t0,
            prompt_tokens=st.m,
            gen_tokens=len(st.emitted),
            reuse_depth=st.depth,
            cache_hit=st.hit,
            mode=st.mode if st.use_recycling else "baseline",
            prompt_similarity=st.sim,
            ttft_s=max(st.t_first - st.t0, 0.0),
        )
