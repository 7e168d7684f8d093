"""Paged block-table pool: token-for-token equivalence with the dense
slot pool (and therefore with serial ``generate``) across every admission
mode, real prefix sharing (refcount > 1, fewer device bytes than the
dense pool), zero host→device traffic on warm-prefix admissions, and the
block-allocator invariants (refcounts, free/live partition, no aliasing).

The dense ``BatchedEngine`` stays the equivalence reference: every paged
behavior is asserted against it (or serial) rather than against golden
outputs.  Property-style allocator tests run only when hypothesis is
installed, mirroring tests/test_slot_pool.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import BlockAllocator, BlockPoolExhausted, BlockTrie
from repro.data.tokenizer import EOS
from repro.models import init_params
from repro.models.cache import cache_bytes
from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                           Engine, PagedEngine)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CACHED = [
    "the quick brown fox jumps over the lazy dog today",
    "what is the capital of france and why",
]
REQUESTS = [
    (CACHED[0] + " and tomorrow", "exact_prefix"),
    ("the quick brown fox jumps over a red fence", "partial_block"),
    ("zzz qqq completely unrelated 12345", "miss"),
    (CACHED[1] + " is it paris", "exact_prefix"),
]


@pytest.fixture(scope="module")
def stack():
    cfg = get_config("dialogpt-medium").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engines(stack, *, max_new=6, max_batch=3, capacity=128):
    cfg, params = stack
    ser = Engine(cfg, params, max_new_tokens=max_new, block_size=8,
                 enable_partial=True)
    ser.precache(CACHED)
    pag = PagedEngine(cfg, params, max_batch=max_batch, capacity=capacity,
                      max_new_tokens=max_new, block_size=8,
                      enable_partial=True)
    pag.precache(CACHED)
    return ser, pag


# ---------------------------------------------------------------------------
# equivalence: paged == dense == serial, all admission modes
# ---------------------------------------------------------------------------
def test_paged_equals_serial_all_modes(stack):
    ser, pag = _engines(stack)
    serial = {p: ser.generate(p) for p, _ in REQUESTS}

    sched = ContinuousBatchingScheduler(pag)
    reqs = [sched.submit(p) for p, _ in REQUESTS]
    sched.run()
    pag.check_invariants()

    for (p, want_mode), req in zip(REQUESTS, reqs):
        s, b = serial[p], req.result
        # the paged tier may upgrade a host hit to resident_block when the
        # prefix became device-resident mid-batch; tokens must not drift
        assert b.mode in (want_mode, "resident_block"), (p, b.mode)
        assert b.text == s.text, (p, b.mode)
        np.testing.assert_array_equal(b.token_ids, s.token_ids)
        assert b.gen_tokens == s.gen_tokens
        assert b.prompt_tokens == s.prompt_tokens


def test_paged_equals_dense_pool(stack):
    """Same workload through the dense slot pool and the paged pool over
    identical recycler contents: identical tokens, request by request."""
    cfg, params = stack
    dense = BatchedEngine(cfg, params, max_batch=3, capacity=128,
                          max_new_tokens=6, block_size=8,
                          enable_partial=True)
    dense.precache(CACHED)
    _, pag = _engines(stack)

    dsched = ContinuousBatchingScheduler(dense)
    dreqs = [dsched.submit(p) for p, _ in REQUESTS]
    dsched.run()
    psched = ContinuousBatchingScheduler(pag)
    preqs = [psched.submit(p) for p, _ in REQUESTS]
    psched.run()

    for (p, _), d, q in zip(REQUESTS, dreqs, preqs):
        assert q.result.text == d.result.text, p
        np.testing.assert_array_equal(q.result.token_ids,
                                      d.result.token_ids)


def test_paged_early_eos_equivalence(stack, monkeypatch):
    """Early-EOS rows free their blocks mid-flight; survivors must keep
    decoding exactly like their serial runs (same greedy remap trick as
    the dense-pool test, patched once for all three paths)."""
    import repro.serving.engine as engine_mod

    def eos_greedy(logits):
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(g % 5 == 1, jnp.int32(EOS), g)

    monkeypatch.setattr(engine_mod, "greedy", eos_greedy)
    ser, pag = _engines(stack, max_new=8)
    serial = {p: ser.generate(p) for p, _ in REQUESTS}
    assert any(r.gen_tokens < 8 and r.token_ids[-1] == EOS
               for r in serial.values()), "remap produced no early EOS"

    sched = ContinuousBatchingScheduler(pag)
    reqs = [sched.submit(p) for p, _ in REQUESTS]
    sched.run()
    pag.check_invariants()
    for (p, _), req in zip(REQUESTS, reqs):
        s, b = serial[p], req.result
        assert b.text == s.text and b.gen_tokens == s.gen_tokens
        np.testing.assert_array_equal(b.token_ids, s.token_ids)


def test_paged_mixed_budgets_and_refill(stack):
    """Mid-flight slot refill over the paged pool: different budgets free
    rows at different steps; outputs stay identical to serial."""
    ser, pag = _engines(stack, max_new=8, max_batch=2)
    prompts = [p for p, _ in REQUESTS] + ["one more cold prompt"]
    budgets = [8, 3, 5, 2, 8]
    serial = [ser.generate(p, max_new_tokens=n)
              for p, n in zip(prompts, budgets)]

    sched = ContinuousBatchingScheduler(pag)
    reqs = [sched.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    sched.run()
    pag.check_invariants()
    assert sched.stats["slot_reuses"] >= 1
    for s, req in zip(serial, reqs):
        assert req.result.text == s.text
        np.testing.assert_array_equal(req.result.token_ids, s.token_ids)


# ---------------------------------------------------------------------------
# sharing: one physical prefix, many tables
# ---------------------------------------------------------------------------
def test_shared_prefix_blocks_refcount_gt_one(stack):
    """Two in-flight requests extending the same cached prompt must NAME
    the same pool blocks (refcount > 1), not hold private copies."""
    cfg, params = stack
    pag = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=8, block_size=8, enable_partial=True)
    pag.precache(CACHED)
    sched = ContinuousBatchingScheduler(pag)
    sched.submit(CACHED[0] + " tonight")
    sched.submit(CACHED[0] + " tomorrow")
    sched.step()                      # both admitted, both still in flight
    pag.check_invariants()

    rows = [set(b for b in pag._tables[i] if b != 0) for i in range(2)]
    both = rows[0] & rows[1]
    assert both, "no pool block is shared between the two tables"
    assert all(pag.allocator.refcount(b) >= 2 for b in both)
    sched.run()
    pag.check_invariants()


def test_paged_uses_fewer_device_bytes_than_dense(stack):
    """At batch 8 the dense pool pays max_batch * capacity slots up
    front; the paged pool's referenced bytes track actual lengths and
    shared prefixes are counted once."""
    cfg, params = stack
    dense = BatchedEngine(cfg, params, max_batch=8, capacity=128,
                          max_new_tokens=6, block_size=8,
                          enable_partial=True)
    dense.precache(CACHED)
    pag = PagedEngine(cfg, params, max_batch=8, capacity=128,
                      max_new_tokens=6, block_size=8, enable_partial=True)
    pag.precache(CACHED)

    sched = ContinuousBatchingScheduler(pag)
    for i in range(8):                # all extend the same cached prompt
        sched.submit(CACHED[0] + f" variant {i}")
    sched.step()
    pag.check_invariants()
    used = pag.device_kv_bytes_in_use()
    dense_bytes = cache_bytes(dense.pool)
    assert used < dense_bytes, (used, dense_bytes)
    sched.run()


def test_warm_admission_no_host_copy(stack):
    """Once a prefix is device-resident, re-admitting it must perform no
    host→device cache copy at all (the L1 zero-copy contract)."""
    _, pag = _engines(stack)
    sched = ContinuousBatchingScheduler(pag)
    sched.submit(CACHED[0] + " first pass")
    sched.run()
    h2d = pag.stats["h2d_copies"]
    assert pag.stats["host_promotions"] >= 1

    sched2 = ContinuousBatchingScheduler(pag)
    r = sched2.submit(CACHED[0] + " second pass")
    sched2.run()
    assert r.result.cache_hit and r.result.mode == "resident_block"
    assert np.isnan(r.result.prompt_similarity)   # no retrieval backs L1
    assert pag.stats["h2d_copies"] == h2d         # zero new host traffic
    pag.check_invariants()


def test_cow_never_mutates_shared_blocks(stack):
    """A divergent admission sharing a prefix must copy the boundary
    block, never write the donor's: the donor's pool content is bitwise
    unchanged after the sharer runs."""
    cfg, params = stack
    pag = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=4, block_size=8, enable_partial=True)
    sched = ContinuousBatchingScheduler(pag)
    sched.submit("the quick brown fox jumps over the lazy dog today")
    sched.run()
    donor_blocks = sorted(pag.trie.blocks())
    before = {
        seg: np.asarray(c["k"][:, donor_blocks])
        for seg, c in pag.pool.items()
    }

    sched2 = ContinuousBatchingScheduler(pag)
    # extends the donor EXACTLY, so the chain includes its partial tail
    # block -> the sharer must CoW it before writing its own suffix
    r = sched2.submit("the quick brown fox jumps over the lazy dog today"
                      " tonight")
    sched2.run()
    assert r.result.cache_hit and r.result.mode == "resident_block"
    assert r.result.reuse_depth % pag.block != 0   # genuinely mid-block
    assert pag.stats["cow_copies"] >= 1
    for seg, c in pag.pool.items():
        np.testing.assert_array_equal(
            np.asarray(c["k"][:, donor_blocks]), before[seg])
    pag.check_invariants()


# ---------------------------------------------------------------------------
# lifecycle and pressure
# ---------------------------------------------------------------------------
def test_pool_exhaustion_rejects_cleanly(stack):
    """A request the allocator cannot promise blocks for is rejected with
    an error (scheduler records it); the rest of the queue proceeds."""
    cfg, params = stack
    pag = PagedEngine(cfg, params, max_batch=2, capacity=64,
                      max_new_tokens=4, block_size=8,
                      num_blocks=6)          # sentinel + 5 usable
    sched = ContinuousBatchingScheduler(pag)
    ok = sched.submit("ab")                  # tiny: 1 block now + later
    bad = sched.submit("a prompt long enough to need many more blocks "
                       "than five")
    sched.run()
    assert ok.result is not None and ok.result.gen_tokens > 0
    assert bad.result is None and "exhausted" in bad.error
    assert sched.stats["rejected"] == 1
    pag.check_invariants()
    assert pag.free_slots() == [0, 1]


def test_trie_eviction_under_pressure(stack):
    """When the free list runs dry, cold L1 prefixes are evicted (LRU)
    instead of failing the admission; tokens stay correct."""
    cfg, params = stack
    ser = Engine(cfg, params, max_new_tokens=4, block_size=8)
    pag = PagedEngine(cfg, params, max_batch=1, capacity=64,
                      max_new_tokens=4, block_size=8, num_blocks=12)
    sched = ContinuousBatchingScheduler(pag)
    prompts = [f"pressure prompt number {i} with padding words" for i in
               range(4)]
    serial = [ser.generate(p) for p in prompts]
    reqs = [sched.submit(p) for p in prompts]
    sched.run()
    assert pag.stats["trie_evictions"] >= 1
    pag.check_invariants()
    for s, r in zip(serial, reqs):
        assert r.result.text == s.text


def test_instant_finish_keeps_prefix_warm(stack):
    """max_new_tokens=1 never occupies a row, but its prompt blocks stay
    indexed in L1 and serve the next admission residentially."""
    _, pag = _engines(stack)
    sched = ContinuousBatchingScheduler(pag)
    req = sched.submit("warm me up please", max_new_tokens=1)
    finished = sched.step()
    assert req in finished and req.result.gen_tokens == 1
    pag.check_invariants()
    assert len(pag.trie) > 0

    r2 = sched.submit("warm me up please and continue")
    sched.run()
    assert r2.result.mode == "resident_block"
    pag.check_invariants()


def test_paged_admit_feeds_host_store(stack):
    """admit=True harvests the row from pool blocks back into the host
    (L2) store at prompt depth, like the dense engines."""
    cfg, params = stack
    pag = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=4, block_size=8)
    sched = ContinuousBatchingScheduler(pag)
    p = "tell me about rivers"
    sched.submit(p, admit=True)
    sched.run()
    assert len(pag.recycler.store) == 1
    follow = pag.recycler.lookup(p + " and lakes too",
                                 pag.tok.encode(p + " and lakes too"))
    assert follow.hit and follow.reuse_depth >= len(pag.tok.encode(p)) - 1


def test_paged_rejects_window(stack):
    cfg, params = stack
    with pytest.raises(NotImplementedError):
        PagedEngine(cfg, params, window=32)


def test_paged_pool_rejects_stateful_arch():
    from repro.models import init_paged_pool
    cfg = get_config("rwkv6-3b").reduced()
    with pytest.raises(NotImplementedError):
        init_paged_pool(cfg, 8, 8, 2, 4)


# ---------------------------------------------------------------------------
# int8 paged pool (kv_quant=True): greedy-identical to the fp pool,
# ~2-4x fewer device bytes, int8-verbatim host promotions
# ---------------------------------------------------------------------------
def _paged(stack, *, quant, max_new=6, max_batch=3, capacity=128):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=max_batch, capacity=capacity,
                      max_new_tokens=max_new, block_size=8,
                      enable_partial=True, kv_quant=quant)
    eng.precache(CACHED)
    return eng


def _run_workload(eng, reqs=REQUESTS, **submit_kw):
    sched = ContinuousBatchingScheduler(eng)
    out = [sched.submit(p, **submit_kw) for p, _ in reqs]
    sched.run()
    eng.check_invariants()
    return out


def test_int8_paged_equals_fp_paged_all_modes(stack):
    """Acceptance: the int8 pool is greedy-token-identical to the fp pool
    on exact/partial/miss admissions of the reduced DialoGPT workload."""
    fp = _paged(stack, quant=False)
    q8 = _paged(stack, quant=True)
    fp_reqs = _run_workload(fp)
    q8_reqs = _run_workload(q8)
    for (p, want), rf, rq in zip(REQUESTS, fp_reqs, q8_reqs):
        assert rq.result.mode == rf.result.mode, p
        assert rq.result.text == rf.result.text, (p, rq.result.mode)
        np.testing.assert_array_equal(rq.result.token_ids,
                                      rf.result.token_ids)
    assert q8.stats["hits"] == fp.stats["hits"] == 3
    # host promotions moved full int8 blocks verbatim
    assert q8.stats["q8_block_promotions"] > 0


def test_int8_paged_early_eos_equivalence(stack, monkeypatch):
    import repro.serving.engine as engine_mod

    def eos_greedy(logits):
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(g % 5 == 1, jnp.int32(EOS), g)

    monkeypatch.setattr(engine_mod, "greedy", eos_greedy)
    fp = _paged(stack, quant=False, max_new=8)
    q8 = _paged(stack, quant=True, max_new=8)
    fp_reqs = _run_workload(fp)
    q8_reqs = _run_workload(q8)
    assert any(r.result.gen_tokens < 8 and r.result.token_ids[-1] == EOS
               for r in fp_reqs), "remap produced no early EOS"
    for rf, rq in zip(fp_reqs, q8_reqs):
        assert rq.result.text == rf.result.text
        assert rq.result.gen_tokens == rf.result.gen_tokens


def test_int8_pool_bytes_reduction(stack):
    """Acceptance: >= 1.8x reduction in device_kv_bytes_in_use vs the fp
    pool for the same workload at the same occupancy."""
    fp = _paged(stack, quant=False, max_batch=4)
    q8 = _paged(stack, quant=True, max_batch=4)
    _run_workload(fp)
    _run_workload(q8)
    # identical greedy trajectories allocate identical block counts
    assert q8.allocator.num_live() == fp.allocator.num_live()
    ratio = fp.device_kv_bytes_in_use() / q8.device_kv_bytes_in_use()
    assert ratio >= 1.8, ratio


def test_int8_pool_layout(stack):
    cfg, _ = stack
    q8 = _paged(stack, quant=True)
    seg = q8.pool["seg0"]
    assert seg["k"].dtype == jnp.int8 and seg["v"].dtype == jnp.int8
    assert seg["k_scale"].dtype == jnp.float32
    bs = q8.block
    assert seg["k_tail"].shape[2] == q8.fp_tail_blocks * bs
    assert seg["k_tail"].dtype == jnp.dtype(cfg.dtype)
    # the int8 host tier is on by default with a residual covering the
    # device fp ring tail
    assert q8.recycler.compress
    assert q8.recycler.compress_residual == (q8.fp_tail_blocks + 1) * bs


def test_int8_harvest_preserves_pool_bits(stack):
    """admit=True on the int8 pool stores the pool's int8 codes verbatim
    (plus an fp residual tail) — harvesting is not a requantization."""
    from repro.core.quant import _QKEY, is_quantized
    q8 = _paged(stack, quant=True)
    sched = ContinuousBatchingScheduler(q8)
    p = "tell me about rivers and their deltas in detail"
    sched.submit(p, admit=True)
    sched.run()
    assert len(q8.recycler.store) == len(CACHED) + 1
    e = q8.recycler.store.get(q8.recycler.store.ids()[-1], touch=False)
    assert is_quantized(e.cache)
    m = len(q8.tok.encode(p))
    split = max(0, m - q8.recycler.compress_residual)
    leaf = e.cache["seg0"]["k"]
    assert leaf[_QKEY].shape[2] == split
    assert leaf[_QKEY].dtype == np.int8
    # the stored codes equal the pool bits for the entry's blocks
    chain = [b for b, _ in q8.trie.lookup(q8.tok.encode(p))[1]]
    pool_k = np.asarray(q8.pool["seg0"]["k"])[:, chain]
    pool_k = pool_k.reshape(pool_k.shape[0], -1, *pool_k.shape[3:])
    np.testing.assert_array_equal(leaf[_QKEY][:, 0],
                                  pool_k[:, :split])


def test_int8_promotes_legacy_quantized_entries_via_fallback(stack):
    """A host entry in the PRE-residual quantized format (only
    __q8__/scale/dtype leaves, e.g. reloaded from an old save_dir) must
    still promote — through the dequant+scatter fallback, not the
    verbatim int8 upload (which needs the ax/cap/tail metadata)."""
    from repro.core import quant
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                      max_new_tokens=5, block_size=8, kv_quant=True)
    eng.precache(CACHED[:1])
    e = eng.recycler.store.get(eng.recycler.store.ids()[0], touch=False)

    def legacy(t):
        if isinstance(t, dict):
            if quant._QKEY in t:
                full = quant.dequantize_tree({"x": t})["x"]
                amax = np.max(np.abs(full.astype(np.float32)), axis=-1,
                              keepdims=True)
                s = (amax / 127.0 + 1e-12).astype(np.float32)
                q = np.clip(np.round(full.astype(np.float32) / s),
                            -127, 127).astype(np.int8)
                return {quant._QKEY: q, "scale": s,
                        "dtype": np.dtype(full.dtype).str}
            return {k: legacy(v) for k, v in t.items()}
        return t

    e.cache = legacy(e.cache)
    # the deliberate format rewrite must re-stamp the integrity digest,
    # or the serve-time corruption check (correctly) drops the entry
    from repro.core.kvstore import cache_digest
    e.digest = cache_digest(e.cache)
    sched = ContinuousBatchingScheduler(eng)
    r = sched.submit(CACHED[0] + " and tomorrow")
    sched.run()
    eng.check_invariants()
    assert r.result.mode == "exact_prefix"
    assert eng.stats["q8_block_promotions"] == 0     # fallback path


def test_paged_converts_dense_quant_host_entries(stack):
    """A host entry in the dense kv_quant layout (native k_scale leaves)
    can't be consumed by the paged admission layouts directly — instead
    of the old honest-miss skip, it is CONVERTED (dequant -> staging
    layout, value-preserving to within half a quant step) on promotion
    and serves the hit; the conversion counter records the event for the
    bench."""
    cfg, params = stack
    from repro.serving import BatchedEngine
    donor = BatchedEngine(cfg, params, max_batch=2, capacity=128,
                          max_new_tokens=4, block_size=8, kv_quant=True)
    donor.precache(CACHED[:1])
    for pm in ("chunked", "staged"):
        pag = PagedEngine(cfg, params, max_batch=2, capacity=128,
                          max_new_tokens=4, block_size=8,
                          recycler=donor.recycler, prefill_mode=pm)
        sched = ContinuousBatchingScheduler(pag)
        r = sched.submit(CACHED[0] + " and tomorrow")
        sched.run()
        assert r.result.mode == "exact_prefix", pm
        assert r.result.reuse_depth > 0
        assert pag.stats["layout_conversions"] == 1, pm
        assert pag.stats["host_promotions"] == 1
        assert r.result.gen_tokens > 0
        pag.check_invariants()


def test_paged_quant_kernel_matches_reference():
    """Fused-dequant kernel == jnp reference gather (int8 pool + fp ring
    tail overlay) across rows at different depths."""
    from repro.kernels import ops
    from repro.models.attention import attend_paged
    rng = np.random.default_rng(5)
    B, NB, bs, H, hkv, dh, R = 3, 12, 8, 4, 2, 16, 2
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, hkv, dh)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, hkv, dh)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.02, size=(NB, bs, hkv)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.02, size=(NB, bs, hkv)),
                     jnp.float32)
    kt = jnp.asarray(rng.normal(size=(B, R * bs, hkv, dh)), jnp.float32)
    vt = jnp.asarray(rng.normal(size=(B, R * bs, hkv, dh)), jnp.float32)
    tables = jnp.asarray([[3, 5, 7, 0], [1, 2, 0, 0], [9, 8, 6, 4]],
                         jnp.int32)
    pos = jnp.asarray([25, 12, 31], jnp.int32)
    cache = {"k": kp, "v": vp, "k_scale": ks, "v_scale": vs,
             "k_tail": kt, "v_tail": vt, "block_tables": tables}
    out = ops.paged_decode_attention_quant(q, kp, vp, ks, vs, kt, vt,
                                           tables, pos, interpret=True)
    ref = attend_paged(q, cache, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_quant_pallas_engine_equivalence(stack):
    """The Pallas int8 decode path produces the same greedy tokens as the
    jnp reference path on a real engine workload."""
    from repro.runtime import Runtime
    cfg, params = stack
    outs = []
    for rt in (Runtime(), Runtime(use_pallas=True)):
        eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                          max_new_tokens=5, block_size=8,
                          enable_partial=True, kv_quant=True, rt=rt)
        eng.precache(CACHED[:1])
        reqs = _run_workload(eng, REQUESTS[:2])
        outs.append([r.result.text for r in reqs])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# kernel: block-table gather matches the reference gather
# ---------------------------------------------------------------------------
KBS, KNBT, KNB = 8, 6, 40            # page size, table width, pool blocks
KIDLE = KNBT * KBS + 5               # an idle row counts past its table


@pytest.mark.parametrize("pages", [None, 4], ids=["rule", "groups_of_4"])
@pytest.mark.parametrize("hkv,group", [(2, 1), (2, 2), (1, 4)],
                         ids=["mha", "gqa", "mqa"])
@pytest.mark.parametrize("pos", [
    [0], [KBS - 1], [KBS], [2 * KBS + 3], [KNBT * KBS - 1], [KIDLE],
    [0, KNBT * KBS - 3, 9, 4 * KBS + 4, 2 * KBS + 3, KIDLE],
], ids=["pos0", "page_end", "page_start", "mid_page", "table_end",
        "idle_past_table", "ragged"])
def test_paged_kernel_matches_reference(pos, hkv, group, pages,
                                       monkeypatch):
    """The row-walk decode kernel == the jnp reference gather: MHA, GQA
    and one kv head (two heads share a slab row, one fills it), positions
    at page edges and the table's end, an idle row whose ``pos`` runs
    past a sentinel-only table, rows of very different lengths in one
    batch, and groups of 4 pages (which do not divide the table) with
    walks that end part-way through a group (``None``: the kernel's own
    page rule).  The kernel is called unjitted, so that each case lowers
    with its own page rule."""
    from repro.kernels import decode_attention
    from repro.models.attention import attend_paged
    if pages is not None:
        monkeypatch.setattr(decode_attention, "_group_pages",
                            lambda *a: pages)
    rng = np.random.default_rng(3)
    B, dh = len(pos), 16
    H = hkv * group
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(KNB, KBS, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(KNB, KBS, hkv, dh)), jnp.float32)
    perm = rng.permutation(np.arange(1, KNB))
    tables = np.zeros((B, KNBT), np.int32)
    for b, p in enumerate(pos):
        if p < KNBT * KBS:              # else idle: sentinel-only table
            n = p // KBS + 1
            tables[b, :n], perm = perm[:n], perm[n:]
    tables, pos = jnp.asarray(tables), jnp.asarray(pos, jnp.int32)
    out = decode_attention.paged_decode_attention(q, kp, vp, tables, pos,
                                                  interpret=True)
    ref = attend_paged(q, {"k": kp, "v": vp, "block_tables": tables}, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_pallas_engine_equivalence(stack):
    """The Pallas fp decode path produces the same greedy tokens as the
    jnp reference path on a real engine workload."""
    from repro.runtime import Runtime
    cfg, params = stack
    outs = []
    for rt in (Runtime(), Runtime(use_pallas=True)):
        eng = PagedEngine(cfg, params, max_batch=2, capacity=128,
                          max_new_tokens=5, block_size=8,
                          enable_partial=True, rt=rt)
        eng.precache(CACHED[:1])
        reqs = _run_workload(eng, REQUESTS[:2])
        outs.append([r.result.text for r in reqs])
    assert outs[0] == outs[1]


def test_decode_pages_walked_counter(stack):
    """Each decode dispatch adds, for every decoding row, its table
    entries through the last valid page, and the width of its table."""
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=3, capacity=128,
                      max_new_tokens=12, block_size=8, enable_partial=True)
    eng.precache(CACHED)
    reqs = _run_workload(eng)
    walked = table = 0
    for r in reqs:
        m, n = r.result.prompt_tokens, r.result.gen_tokens
        # the first token comes from admission; decode step k writes and
        # attends through position m + k - 1
        walked += sum(min(p // 8, eng.nbt - 1) + 1
                      for p in range(m, m + n - 1))
        table += (n - 1) * eng.nbt
    assert table > 0 and walked < table
    assert eng.stats["decode_pages_walked"] == walked
    assert eng.stats["decode_table_pages"] == table


def test_paged_gather_equals_row_alone():
    """Each row's paged attention equals the same row attended over a
    dense buffer built from its blocks (no cross-table leakage)."""
    from repro.models.attention import attend_direct, attend_paged
    rng = np.random.default_rng(4)
    B, NB, bs, H, hkv, dh = 3, 10, 4, 2, 1, 8
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, hkv, dh)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    pos = jnp.asarray([11, 6, 2], jnp.int32)
    out = attend_paged(q, {"k": kp, "v": vp, "block_tables": tables}, pos)
    for b in range(B):
        k = kp[tables[b]].reshape(1, -1, hkv, dh)
        v = vp[tables[b]].reshape(1, -1, hkv, dh)
        kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        solo = attend_direct(q[b:b + 1], k, v, pos[b:b + 1, None], kv_pos,
                             causal=True)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(solo[0]),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# allocator invariants (property-style; skipped without hypothesis)
# ---------------------------------------------------------------------------
def test_allocator_basics():
    a = BlockAllocator(6, 8)
    b1, b2 = a.alloc(), a.alloc()
    assert b1 != b2 and 0 not in (b1, b2)
    a.ref(b1)
    assert a.refcount(b1) == 2
    a.unref(b1)
    a.unref(b1)
    assert a.refcount(b1) == 0 and b1 in a.free_blocks()
    a.check()
    with pytest.raises(ValueError):
        a.unref(b1)                          # double free
    with pytest.raises(ValueError):
        a.ref(0)                             # sentinel is pinned
    for _ in range(4):
        a.alloc()
    with pytest.raises(BlockPoolExhausted):
        a.alloc()


def test_trie_register_lookup_evict():
    trie = BlockTrie(4)
    a = BlockAllocator(10, 4)
    ids = list(range(10))
    blocks = [a.alloc(), a.alloc(), a.alloc()]
    for b in trie.register(ids, 10, blocks):
        a.ref(b)
    depth, chain = trie.lookup(ids)
    assert depth == 10 and [b for b, _ in chain] == blocks
    assert chain[-1][1] == 2                 # partial tail fill
    d2, c2 = trie.lookup(ids[:6] + [99, 98])
    assert d2 == 4 and [b for b, _ in c2] == blocks[:1]
    # evict: only refcount-1 blocks, leaves first
    for b in blocks:
        a.unref(b)                           # drop the "table" refs
    dropped = trie.evict(10, lambda b: a.refcount(b) == 1)
    assert set(dropped) == set(blocks)
    assert trie.lookup(ids)[0] == 0


if HAVE_HYPOTHESIS:
    class TestAllocatorProperty:
        @given(ops=st.lists(st.tuples(st.integers(0, 2),
                                      st.integers(0, 30)),
                            min_size=1, max_size=200),
               nb=st.integers(2, 12))
        @settings(max_examples=60, deadline=None)
        def test_random_op_sequences_hold_invariants(self, ops, nb):
            """For ANY interleaving of alloc/ref/unref: refcounts >= 0,
            free ∪ live partitions the pool, and a block is never handed
            out twice without an intervening free."""
            a = BlockAllocator(nb, 8)
            held = []                        # (block, holders) we own
            for op, pick in ops:
                if op == 0:                  # alloc
                    try:
                        held.append([a.alloc(), 1])
                    except BlockPoolExhausted:
                        assert a.num_free() == 0
                elif op == 1 and held:       # ref
                    h = held[pick % len(held)]
                    a.ref(h[0])
                    h[1] += 1
                elif op == 2 and held:       # unref
                    i = pick % len(held)
                    h = held[i]
                    left = a.unref(h[0])
                    h[1] -= 1
                    assert left == h[1]
                    if h[1] == 0:
                        del held[i]
                a.check()
                for b, n in held:
                    assert a.refcount(b) == n
            live_expected = {b for b, _ in held}
            assert a.live_blocks() == live_expected

    class TestPagedEngineInvariantFuzz:
        @given(seed=st.integers(0, 2**16))
        @settings(max_examples=5, deadline=None)
        def test_scheduler_run_holds_invariants(self, seed):
            """Random mixed workloads through the scheduler: after every
            step, refcounts equal table+trie holders exactly and the free
            list never aliases a live block."""
            cfg = get_config("dialogpt-medium").reduced()
            params = init_params(cfg, jax.random.PRNGKey(0))
            rng = np.random.default_rng(seed)
            pag = PagedEngine(cfg, params, max_batch=2, capacity=64,
                              max_new_tokens=4, block_size=8,
                              enable_partial=True, num_blocks=20)
            sched = ContinuousBatchingScheduler(pag)
            words = ["alpha", "beta", "gamma", "delta"]
            for i in range(6):
                n = rng.integers(2, 6)
                sched.submit(" ".join(rng.choice(words) for _ in range(n)),
                             max_new_tokens=int(rng.integers(1, 5)),
                             admit=bool(rng.integers(0, 2)))
            while sched.pending() or sched.in_flight:
                sched.step()
                pag.check_invariants()
else:  # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_allocator_property():
        pass
