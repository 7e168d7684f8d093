"""Ragged packed multi-admission prefill + SLO-aware admission.

The packed admission route (``prefill_mode="packed"``) advances EVERY
pending admission's chunk step in ONE ragged packed-QKV dispatch per
engine step.  This file holds it to the same discipline the chunked route
was held to: greedy decode is token-for-token identical to the chunked,
staged and serial paths (fp and int8, exact/partial/miss admissions,
early EOS); the packed kernel matches the jnp reference; the packed
writer matches per-segment chunk writes; the prefill-compile count is
bounded by the fixed packed-bucket ladder — independent of the number of
CONCURRENT admissions, not just of suffix lengths; and the ragged segment
descriptor construction satisfies its invariants (no overlap, full
coverage, block alignment) for ANY workload (hypothesis).

The satellites ride along: per-tenant admission quotas, cache-aware
refill, and per-row repetition/presence penalties in ``sample_batched``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.tokenizer import EOS
from repro.models import init_params
from repro.serving import (ContinuousBatchingScheduler, Engine,
                           PagedEngine)
from repro.serving.paged import SENTINEL, pack_admission_segments

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


def _paged(stack, *, prefill_mode, quant=False, max_new=6, max_batch=3,
           capacity=128, precache=CACHED, **kw):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=max_batch, capacity=capacity,
                      max_new_tokens=max_new, block_size=8,
                      enable_partial=True, kv_quant=quant,
                      prefill_mode=prefill_mode, **kw)
    if precache:
        eng.precache(precache)
    return eng


def _run(eng, prompts, sched_kw=None, **submit_kw):
    sched = ContinuousBatchingScheduler(eng, **(sched_kw or {}))
    reqs = [sched.submit(p, **submit_kw) for p in prompts]
    sched.run()
    eng.check_invariants()
    return reqs


# ---------------------------------------------------------------------------
# 4-way token identity: packed == chunked == staged == serial
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_packed_equals_chunked_and_staged(stack, quant):
    """Acceptance: packed greedy decode is token-identical to the chunked
    and staged routes on the reduced DialoGPT workload, fp and int8,
    across exact/partial/miss admissions — and the packed engine issued
    ONE prefill dispatch per engine step, not one per admission."""
    outs = {}
    for mode in ("staged", "chunked", "packed"):
        eng = _paged(stack, prefill_mode=mode, quant=quant)
        outs[mode] = (_run(eng, [p for p, _ in REQUESTS]), eng)
    for (p, _), rs, rc, rp in zip(REQUESTS, outs["staged"][0],
                                  outs["chunked"][0], outs["packed"][0]):
        assert rp.result.text == rc.result.text == rs.result.text, p
        np.testing.assert_array_equal(rp.result.token_ids,
                                      rc.result.token_ids)
        np.testing.assert_array_equal(rp.result.token_ids,
                                      rs.result.token_ids)
    eng = outs["packed"][1]
    assert eng.stats["prefill_packed_steps"] > 0
    assert eng.stats["staging_prefills"] == 0
    # one dispatch per packed step, every admission advanced inside it
    assert (eng.stats["prefill_dispatches"]
            == eng.stats["prefill_packed_steps"])
    assert eng.stats["prefill_chunks"] >= len(REQUESTS)
    # the chunked engine paid one dispatch per admission chunk
    ceng = outs["chunked"][1]
    assert ceng.stats["prefill_dispatches"] == ceng.stats["prefill_chunks"]


def test_packed_equals_serial_multi_chunk(stack):
    """A small chunk size forces every admission through SEVERAL packed
    steps interleaved with decode; fp outputs stay identical to the
    serial engine."""
    cfg, params = stack
    ser = Engine(cfg, params, max_new_tokens=6, block_size=8,
                 enable_partial=True)
    ser.precache(CACHED)
    serial = {p: ser.generate(p) for p, _ in REQUESTS}
    eng = _paged(stack, prefill_mode="packed", prefill_chunk=16)
    reqs = _run(eng, [p for p, _ in REQUESTS])
    assert eng.stats["prefill_packed_steps"] > 1
    for (p, _), r in zip(REQUESTS, reqs):
        np.testing.assert_array_equal(r.result.token_ids,
                                      serial[p].token_ids)


def test_packed_early_eos_equivalence(stack, monkeypatch):
    """Early-EOS rows free their blocks while neighbors are still being
    packed into the same dispatch; survivors decode exactly like
    chunked."""
    import repro.serving.engine as engine_mod

    def eos_greedy(logits):
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(g % 5 == 1, jnp.int32(EOS), g)

    monkeypatch.setattr(engine_mod, "greedy", eos_greedy)
    chunked = _paged(stack, prefill_mode="chunked", max_new=8)
    packed = _paged(stack, prefill_mode="packed", max_new=8)
    creqs = _run(chunked, [p for p, _ in REQUESTS])
    preqs = _run(packed, [p for p, _ in REQUESTS])
    assert any(r.result.gen_tokens < 8 and r.result.token_ids[-1] == EOS
               for r in creqs), "remap produced no early EOS"
    for rc, rp in zip(creqs, preqs):
        assert rp.result.text == rc.result.text
        assert rp.result.gen_tokens == rc.result.gen_tokens
        np.testing.assert_array_equal(rp.result.token_ids,
                                      rc.result.token_ids)


# ---------------------------------------------------------------------------
# compile count: independent of CONCURRENT-admission count
# ---------------------------------------------------------------------------
def test_packed_compiles_independent_of_admission_count(stack):
    """Acceptance: admitting 1 request or max_batch requests at once
    reuses the SAME packed executables — the compile count is bounded by
    the fixed packed-bucket ladder, never by concurrency or lengths."""
    prompts = [f"prompt of a distinct length {'x' * i}" for i in
               (0, 3, 7, 11)]
    eng = _paged(stack, prefill_mode="packed", max_batch=4, precache=None)
    _run(eng, prompts[:1])                 # 1 concurrent admission
    assert eng.prefill_compiles() <= len(eng.packed_buckets)
    seen = eng.prefill_compiles()
    _run(eng, prompts)                     # max_batch concurrent, new
    assert eng.prefill_compiles() <= len(eng.packed_buckets)  # lengths
    extra = eng.prefill_compiles() - seen
    # new BUCKETS may compile (bigger packed totals), but concurrency
    # itself must not: repeat the burst -> zero new executables
    _run(eng, [p + " again" for p in prompts])
    assert eng.prefill_compiles() == seen + extra


# ---------------------------------------------------------------------------
# kernel == jnp reference (fp and int8) and writer == per-segment writes
# ---------------------------------------------------------------------------
def _two_segment_pack():
    """Two ragged segments + a pad segment in a T=32 packed buffer:
    seg 0 (row 0) at depth 16 with a 13-valid 16-token chunk, seg 1
    (row 1) at depth 8 with a 5-valid 8-token chunk, 8 pad tokens."""
    rows = jnp.asarray([0, 1, 0], jnp.int32)
    tables = jnp.asarray([[3, 5, 7, 9, 0, 0],
                          [4, 6, 0, 0, 0, 0],
                          [0, 0, 0, 0, 0, 0]], jnp.int32)
    c0s = jnp.asarray([16, 8, 0], jnp.int32)
    w_floors = jnp.asarray([0, 0, 0], jnp.int32)
    valids = jnp.asarray([13, 5, 0], jnp.int32)
    q_offs = jnp.asarray([0, 16, 24], jnp.int32)
    seg_ids = jnp.asarray([0] * 16 + [1] * 8 + [2] * 8, jnp.int32)
    return rows, tables, c0s, w_floors, valids, q_offs, seg_ids


def _desc(c0s, w_floors, q_offs, seg_ids, bs):
    tile_seg = seg_ids[::bs]
    w_effs = jnp.maximum(w_floors, c0s)
    return jnp.stack([tile_seg, c0s[tile_seg], w_effs[tile_seg],
                      q_offs[tile_seg] // bs])


def test_packed_kernel_matches_reference_fp():
    from repro.kernels import ops
    from repro.models.attention import attend_paged_prefill_packed
    rng = np.random.default_rng(21)
    NB, bs, H, hkv, dh, T = 16, 8, 4, 2, 16, 32
    rows, tables, c0s, w_floors, valids, q_offs, seg_ids = _two_segment_pack()
    kp = jnp.asarray(rng.normal(size=(NB, bs, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, T, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(1, T, hkv, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(1, T, hkv, dh)), jnp.float32)
    cache = {"k": kp, "v": vp,
             "block_tables": jnp.zeros((1, 6), jnp.int32)}
    ref = attend_paged_prefill_packed(q, kc, vc, cache, rows, tables, c0s,
                                      w_floors, q_offs, seg_ids)
    out = ops.paged_prefill_attention_packed(
        q, kc, vc, kp, vp, tables, _desc(c0s, w_floors, q_offs, seg_ids, bs),
        interpret=True)
    # compare VALID tokens only (chunk padding past a segment's valids is
    # never read by the engine)
    for i in range(2):
        o, n = int(q_offs[i]), int(valids[i])
        np.testing.assert_allclose(np.asarray(out[0, o:o + n]),
                                   np.asarray(ref[0, o:o + n]), atol=1e-5)
    # and against the per-chunk reference segment by segment — packing
    # must not leak anything across segments
    from repro.models.attention import attend_paged_prefill
    for i in range(2):
        o = int(q_offs[i])
        C = [16, 8][i]                        # seg chunk sizes
        per = attend_paged_prefill(
            q[:, o:o + C], kc[:, o:o + C], vc[:, o:o + C],
            cache, int(rows[i]), tables[i], int(c0s[i]),
            max(int(w_floors[i]), int(c0s[i])))
        n = int(valids[i])
        np.testing.assert_allclose(np.asarray(out[0, o:o + n]),
                                   np.asarray(per[0, :n]), atol=1e-5)


def test_packed_kernel_matches_reference_quant():
    from repro.kernels import ops
    from repro.models.attention import attend_paged_prefill_packed
    rng = np.random.default_rng(22)
    NB, bs, H, hkv, dh, T, R = 16, 8, 4, 2, 16, 32, 2
    rows, tables, c0s, w_floors, valids, q_offs, seg_ids = _two_segment_pack()
    kp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, hkv, dh)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, hkv, dh)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.02, size=(NB, bs, hkv)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.02, size=(NB, bs, hkv)),
                     jnp.float32)
    kt = jnp.asarray(rng.normal(size=(2, R * bs, hkv, dh)), jnp.float32)
    vt = jnp.asarray(rng.normal(size=(2, R * bs, hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, T, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(1, T, hkv, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(1, T, hkv, dh)), jnp.float32)
    cache = {"k": kp, "v": vp, "k_scale": ks, "v_scale": vs,
             "k_tail": kt, "v_tail": vt,
             "block_tables": jnp.zeros((2, 6), jnp.int32)}
    ref = attend_paged_prefill_packed(q, kc, vc, cache, rows, tables, c0s,
                                      w_floors, q_offs, seg_ids)
    out = ops.paged_prefill_attention_packed_quant(
        q, kc, vc, kp, vp, ks, vs, kt[rows], vt[rows], tables,
        _desc(c0s, w_floors, q_offs, seg_ids, bs), interpret=True)
    for i in range(2):
        o, n = int(q_offs[i]), int(valids[i])
        np.testing.assert_allclose(np.asarray(out[0, o:o + n]),
                                   np.asarray(ref[0, o:o + n]), atol=1e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_packed_writer_matches_per_segment_writes(quant):
    """The one fused packed scatter leaves the pool bitwise identical to
    per-segment ``paged_prefill_write`` calls on every NON-SENTINEL
    block.  (Both paths scribble chunk padding into sentinel block 0 —
    with different values, by design — so block 0 is excluded.)"""
    from repro.models.attention import (init_paged_kv_cache,
                                        paged_prefill_write,
                                        paged_prefill_write_packed)
    rng = np.random.default_rng(23)
    NB, bs, hkv, dh, T = 16, 8, 2, 16, 32
    rows, tables, c0s, w_floors, valids, q_offs, seg_ids = _two_segment_pack()
    kc = jnp.asarray(rng.normal(size=(1, T, hkv, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(1, T, hkv, dh)), jnp.float32)

    def fresh():
        return init_paged_kv_cache(NB, bs, hkv, dh, jnp.float32,
                                   max_batch=2, max_blocks_per_seq=6,
                                   quant=quant)

    packed = paged_prefill_write_packed(fresh(), kc, vc, rows, tables, c0s,
                                        w_floors, valids, q_offs, seg_ids)
    serial = fresh()
    for i in range(2):
        o = int(q_offs[i])
        C = [16, 8][i]
        serial = paged_prefill_write(serial, kc[:, o:o + C], vc[:, o:o + C],
                                     int(rows[i]), tables[i], int(c0s[i]),
                                     int(w_floors[i]), int(valids[i]))
    for key in packed:
        if key == "block_tables":
            continue
        a, b = np.asarray(packed[key]), np.asarray(serial[key])
        if key in ("k_tail", "v_tail"):
            np.testing.assert_array_equal(a, b, err_msg=key)
        else:
            np.testing.assert_array_equal(a[1:], b[1:], err_msg=key)


# ---------------------------------------------------------------------------
# hypothesis: ragged segment descriptor invariants
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    class TestPackDescriptorProperty:
        @given(data=st.data(), n_segs=st.integers(1, 4),
               bs=st.sampled_from([4, 8]))
        @settings(max_examples=200, deadline=None)
        def test_pack_covers_exactly_once_block_aligned(self, data, n_segs,
                                                        bs):
            """For ANY admission mix: segments tile the packed buffer
            with no overlap and full coverage, every segment start is
            block-aligned, valid tokens land verbatim, the bucket is the
            smallest that fits, and the trailing pad segment is inert
            (row 0, all-sentinel table, zero valids)."""
            NBt = 8
            segs = []
            for s in range(n_segs):
                blocks = data.draw(st.integers(1, 4), label=f"blocks{s}")
                C = blocks * bs
                n_valid = data.draw(st.integers(1, C), label=f"valid{s}")
                c0 = bs * data.draw(st.integers(0, NBt - blocks),
                                    label=f"c0b{s}")
                w_floor = data.draw(st.integers(0, c0), label=f"wf{s}")
                toks = np.arange(1000 * s, 1000 * s + n_valid, dtype=np.int32)
                tbl = np.arange(1 + s * NBt, 1 + (s + 1) * NBt,
                                dtype=np.int32)
                segs.append((s, tbl, c0, w_floor, n_valid, C, toks))
            max_bucket = 4 * 4 * bs * n_segs
            buckets = sorted({bs * (1 << i) for i in range(12)
                              if bs * (1 << i) <= max_bucket}
                             | {max_bucket})
            pk = pack_admission_segments(segs, block_size=bs,
                                         buckets=buckets,
                                         max_segments=4, table_width=NBt)
            total = sum(C for *_, C, _t in segs)
            T = pk["tokens"].shape[1]
            assert T in buckets and T >= total
            assert T == min(b for b in buckets if b >= total)  # smallest fit
            # no overlap + full coverage: seg i owns exactly
            # [q_offs[i], q_offs[i] + C_i), pad owns the rest
            off = 0
            for i, (_row, _tbl, _c0, _wf, n_valid, C, toks) in \
                    enumerate(segs):
                assert pk["q_offs"][i] == off
                assert off % bs == 0                       # block-aligned
                np.testing.assert_array_equal(
                    pk["seg_ids"][off:off + C], i)
                np.testing.assert_array_equal(
                    pk["tokens"][0, off:off + n_valid], toks)
                assert pk["valids"][i] == n_valid
                off += C
            assert off == total
            np.testing.assert_array_equal(pk["seg_ids"][total:],
                                          len(segs))
            # pad segment is inert
            pad = len(segs)
            assert pk["rows"][pad] == 0 and pk["valids"][pad] == 0
            assert (pk["tables"][pad] == SENTINEL).all()
            assert pk["q_offs"][pad] == total
            # unused segment slots (between pad and max_segments) too
            for i in range(len(segs), 5):
                assert pk["valids"][i] == 0

        @given(total_blocks=st.integers(17, 64))
        @settings(max_examples=50, deadline=None)
        def test_pack_rejects_oversize(self, total_blocks):
            bs = 8
            toks = np.zeros((total_blocks * bs,), np.int32)
            seg = (0, np.full((4,), 1, np.int32), 0, 0, total_blocks * bs,
                   total_blocks * bs, toks)
            with pytest.raises(ValueError):
                pack_admission_segments([seg], block_size=bs,
                                        buckets=[8 * 16],
                                        max_segments=1, table_width=4)
else:  # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_pack_properties():
        pass


# ---------------------------------------------------------------------------
# satellites: tenant quotas, cache-aware refill, penalties
# ---------------------------------------------------------------------------
def test_tenant_quota_denies_admit_not_serving(stack):
    """An over-quota tenant's requests still DECODE; only their L2
    admission is downgraded.  Other tenants are unaffected."""
    eng = _paged(stack, prefill_mode="packed", precache=None)
    sched = ContinuousBatchingScheduler(eng, tenant_quotas={"small": 1})
    store = eng.recycler.store
    # quota reads LIVE usage at admit time, so serve sequentially: the
    # first request's entry must land before the second is admitted
    reqs = []
    for p, _ in REQUESTS[:2]:
        reqs.append(sched.submit(p, tenant="small", admit=True))
        sched.run()
    eng.check_invariants()
    for r in reqs:
        assert r.result is not None and r.error is None
    # first admit landed (usage was 0 < quota), second was denied
    assert store.tenant_usage("small") > 0
    assert len(store) == 1
    assert sched.stats["quota_denied_admits"] == 1
    other = sched.submit(REQUESTS[2][0], tenant="big", admit=True)
    sched.run()
    assert other.result is not None
    assert store.tenant_usage("big") > 0


def test_cache_aware_refill_prefers_resident_prefix(stack):
    """With a warm trie, cache_aware admission picks the queued request
    with the deepest resident prefix ahead of arrival order; an all-cold
    queue degenerates to exact FIFO."""
    eng = _paged(stack, prefill_mode="packed", max_batch=1, precache=None)
    warm = CACHED[0]
    sched = ContinuousBatchingScheduler(eng, admission_policy="cache_aware")
    sched.submit(warm)
    sched.run()                                     # warm the trie
    cold1 = sched.submit("zzz cold request number one")
    hot = sched.submit(warm + " plus a warm suffix")
    cold2 = sched.submit("another cold request entirely")
    sched.run()
    eng.check_invariants()
    assert sched.stats["cache_aware_picks"] >= 1
    assert hot.admit_t < cold1.admit_t              # warm jumped the queue
    assert cold1.admit_t < cold2.admit_t            # cold ties stay FIFO
    # identical outputs to plain FIFO ordering on a fresh engine
    eng2 = _paged(stack, prefill_mode="packed", max_batch=1, precache=None)
    reqs = _run(eng2, [warm, cold1.prompt, hot.prompt, cold2.prompt])
    for a, b in zip((cold1, hot, cold2), reqs[1:]):
        np.testing.assert_array_equal(a.result.token_ids,
                                      b.result.token_ids)


def test_sampling_penalties_rowwise():
    """Per-row repetition/presence penalties: one fused scatter over each
    row's generated set; zero-penalty rows are BIT-identical to the
    un-penalised path (greedy included); -1 padding is inert."""
    from repro.serving.sampling import (apply_penalties, greedy,
                                        sample_batched)
    rng = jax.random.PRNGKey(3)
    logits = jnp.asarray(np.random.default_rng(5).normal(size=(3, 24)),
                         jnp.float32)
    gen = jnp.asarray([[1, 2, -1, -1], [0, 23, 5, 5], [-1, -1, -1, -1]],
                      jnp.int32)
    # statically inert -> transform skipped, greedy bit-identical
    np.testing.assert_array_equal(
        np.asarray(sample_batched(logits, rng, temperature=0.0)),
        np.asarray(sample_batched(logits, rng, temperature=0.0,
                                  repetition_penalty=1.0,
                                  presence_penalty=0.0, gen_tokens=gen)))
    # per-row: row 1 penalised only; row 2 all-pad -> untouched
    rp = jnp.asarray([1.0, 3.0, 3.0], jnp.float32)
    out = apply_penalties(logits, gen, repetition_penalty=rp,
                          presence_penalty=jnp.asarray([0., .5, .5]))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(logits[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(logits[2]))
    for tok in (0, 5, 23):
        assert float(out[1, tok]) < float(logits[1, tok])
    untouched = [t for t in range(24) if t not in (0, 5, 23)]
    np.testing.assert_array_equal(np.asarray(out[1, untouched]),
                                  np.asarray(logits[1, untouched]))
    # a strong repetition penalty steers greedy off its repeated argmax
    g0 = greedy(logits)
    g1 = sample_batched(logits, rng, temperature=0.0,
                        repetition_penalty=100.0,
                        gen_tokens=jnp.tile(g0[:, None], (1, 4)))
    assert not np.array_equal(np.asarray(g0), np.asarray(g1))
