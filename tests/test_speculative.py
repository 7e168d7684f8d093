"""Self-speculative decoding on the paged pool: the sparse-view drafter +
single-dispatch verifier is token-for-token identical to plain greedy
decode — the equivalence oracle — across exact/partial/miss admissions,
fp and int8 pools, chunked and staged prefill, and early EOS; rollback is
exact for ARBITRARY draft tokens (a hypothesis property substitutes
random drafts and the output still cannot drift, with allocator/table/
ring invariants holding after every step); the batched verify kernel
matches the jnp reference; and ``sample_batched`` short-circuits concrete
all-greedy batches.

Plain greedy decode (``speculative=False``) is the reference baseline
throughout — the same diff-the-outputs discipline the chunked-prefill
suite uses against the staged path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.tokenizer import EOS
from repro.models import init_params
from repro.serving import ContinuousBatchingScheduler, PagedEngine
from repro.serving.sampling import greedy, sample_batched

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


def _paged(stack, *, spec, prefill_mode="chunked", quant=False, max_new=8,
           max_batch=3, capacity=128, precache=CACHED, **kw):
    cfg, params = stack
    eng = PagedEngine(cfg, params, max_batch=max_batch, capacity=capacity,
                      max_new_tokens=max_new, block_size=8,
                      enable_partial=True, kv_quant=quant,
                      prefill_mode=prefill_mode, speculative=spec, **kw)
    if precache:
        eng.precache(precache)
    return eng


def _run(eng, prompts, **submit_kw):
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, **submit_kw) for p in prompts]
    while sched.pending() or sched.in_flight:
        sched.step()
        eng.check_invariants()           # holds mid-flight, every step
    return reqs


# ---------------------------------------------------------------------------
# equivalence oracle: speculative greedy == plain greedy, everywhere
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("mode", ["chunked", "staged"])
def test_spec_matches_plain(stack, quant, mode):
    """Acceptance: speculative decode emits the exact token sequence of
    non-speculative greedy decode for every admission mode, both pool
    dtypes, both prefill routes — drafts only buy steps, never change
    them."""
    plain = _paged(stack, spec=False, prefill_mode=mode, quant=quant)
    spec = _paged(stack, spec=True, prefill_mode=mode, quant=quant)
    preqs = _run(plain, [p for p, _ in REQUESTS])
    sreqs = _run(spec, [p for p, _ in REQUESTS])
    for (p, _), rp, rs in zip(REQUESTS, preqs, sreqs):
        assert rs.result.text == rp.result.text, p
        np.testing.assert_array_equal(rs.result.token_ids,
                                      rp.result.token_ids)
    assert spec.stats["spec_rounds"] > 0
    assert (spec.stats["spec_emitted_tokens"]
            > spec.stats["spec_rounds"]), "no draft was ever accepted"


@pytest.mark.parametrize("gamma", [1, 3, 6])
def test_spec_matches_plain_across_gamma(stack, gamma):
    """The identity holds for any draft depth, including gamma = 1
    (degenerate: one draft + bonus) and gamma spanning > 1 block."""
    plain = _paged(stack, spec=False)
    spec = _paged(stack, spec=True, gamma=gamma)
    preqs = _run(plain, [p for p, _ in REQUESTS[:2]])
    sreqs = _run(spec, [p for p, _ in REQUESTS[:2]])
    for rp, rs in zip(preqs, sreqs):
        np.testing.assert_array_equal(rs.result.token_ids,
                                      rp.result.token_ids)
    assert spec.stats["spec_rounds"] > 0


def test_spec_early_eos_equivalence(stack, monkeypatch):
    """A verifier target remapped to EOS mid-bundle truncates the burst
    exactly where plain decode would stop: finished rows release their
    blocks (reserved ones included) while survivors keep speculating."""
    import repro.serving.engine as engine_mod

    def eos_greedy(logits):
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(g % 5 == 1, jnp.int32(EOS), g)

    monkeypatch.setattr(engine_mod, "greedy", eos_greedy)
    plain = _paged(stack, spec=False, max_new=10)
    spec = _paged(stack, spec=True, max_new=10)
    preqs = _run(plain, [p for p, _ in REQUESTS])
    sreqs = _run(spec, [p for p, _ in REQUESTS])
    assert any(r.result.gen_tokens < 10 and r.result.token_ids[-1] == EOS
               for r in preqs), "remap produced no early EOS"
    for rp, rs in zip(preqs, sreqs):
        assert rs.result.gen_tokens == rp.result.gen_tokens
        np.testing.assert_array_equal(rs.result.token_ids,
                                      rp.result.token_ids)


def test_spec_sampled_rows_fall_back(stack):
    """Speculation is greedy-only: any sampled row in the pool parks the
    whole batch on the plain path (counted as fallback steps), and the
    engine still completes correctly."""
    eng = _paged(stack, spec=True)
    reqs = _run(eng, [p for p, _ in REQUESTS[:2]],
                temperature=0.8)
    assert all(r.result is not None for r in reqs)
    assert eng.stats["spec_rounds"] == 0
    assert eng.stats["spec_fallback_steps"] > 0


def test_spec_pallas_engine_equivalence(stack):
    """The Pallas verify-kernel path emits the same greedy tokens as the
    jnp reference path on a real speculative workload (fp and int8)."""
    from repro.runtime import Runtime
    for quant in (False, True):
        outs = []
        for rt in (Runtime(), Runtime(use_pallas=True)):
            eng = _paged(stack, spec=True, quant=quant, max_batch=2,
                         max_new=6, precache=CACHED[:1], rt=rt)
            reqs = _run(eng, [p for p, _ in REQUESTS[:2]])
            outs.append([r.result.text for r in reqs])
        assert outs[0] == outs[1], ("pallas vs jnp", quant)


# ---------------------------------------------------------------------------
# the all-greedy fast path
# ---------------------------------------------------------------------------
def test_sample_batched_all_greedy_fast_path():
    """A concrete all-zero temperature vector short-circuits to argmax —
    rng-independent — while any hot row still samples; the Tracer guard
    keeps the check out of traced code paths."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    t0 = np.zeros((4,), np.float32)
    a = sample_batched(logits, jax.random.PRNGKey(0), temperature=t0)
    b = sample_batched(logits, jax.random.PRNGKey(9), temperature=t0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(greedy(logits)))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # mixed batch: greedy rows stay pinned, hot rows draw
    tm = np.asarray([0.0, 1.0, 0.0, 1.0], np.float32)
    c = sample_batched(logits, jax.random.PRNGKey(0), temperature=tm)
    np.testing.assert_array_equal(np.asarray(c)[[0, 2]],
                                  np.asarray(greedy(logits))[[0, 2]])
    # the guard must not force a value under jit
    jitted = jax.jit(lambda lg, k, t: sample_batched(lg, k, temperature=t))
    d = jitted(logits, jax.random.PRNGKey(0), jnp.asarray(t0))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(greedy(logits)))


# ---------------------------------------------------------------------------
# kernel == reference
# ---------------------------------------------------------------------------
def test_verify_kernel_matches_reference_fp():
    from repro.kernels import ops
    from repro.models.attention import attend_paged_verify
    rng = np.random.default_rng(21)
    NB, bs, H, hkv, dh, NBt, B, Cv = 12, 8, 4, 2, 16, 6, 2, 8
    kp = jnp.asarray(rng.normal(size=(NB, bs, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, hkv, dh)), jnp.float32)
    tbl = jnp.asarray([[3, 5, 7, 9, 0, 0], [1, 2, 4, 6, 8, 10]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Cv, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Cv, hkv, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Cv, hkv, dh)), jnp.float32)
    c0s = jnp.asarray([29, 40], jnp.int32)   # mid-block + block-aligned
    cache = {"k": kp, "v": vp, "block_tables": tbl}
    ref = attend_paged_verify(q, kc, vc, cache, c0s)
    out = ops.paged_verify_attention(q, kc, vc, kp, vp, tbl, c0s,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_verify_kernel_matches_reference_quant():
    from repro.kernels import ops
    from repro.models.attention import attend_paged_verify
    rng = np.random.default_rng(22)
    NB, bs, H, hkv, dh, NBt, B, Cv, R = 12, 8, 4, 2, 16, 6, 2, 8, 2
    kp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, hkv, dh)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, hkv, dh)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.02, size=(NB, bs, hkv)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.02, size=(NB, bs, hkv)),
                     jnp.float32)
    # live ring (draft-polluted; must NOT be read) vs pre-round snapshot
    kt_live = jnp.asarray(rng.normal(size=(B, R * bs, hkv, dh)), jnp.float32)
    vt_live = jnp.asarray(rng.normal(size=(B, R * bs, hkv, dh)), jnp.float32)
    kt_snap = jnp.asarray(rng.normal(size=(B, R * bs, hkv, dh)), jnp.float32)
    vt_snap = jnp.asarray(rng.normal(size=(B, R * bs, hkv, dh)), jnp.float32)
    tbl = jnp.asarray([[3, 5, 7, 9, 0, 0], [1, 2, 4, 6, 8, 10]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Cv, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Cv, hkv, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Cv, hkv, dh)), jnp.float32)
    cache = {"k": kp, "v": vp, "k_scale": ks, "v_scale": vs,
             "k_tail": kt_live, "v_tail": vt_live,
             "k_tail_snap": kt_snap, "v_tail_snap": vt_snap,
             "block_tables": tbl}
    for c0s in (jnp.asarray([29, 40], jnp.int32),
                jnp.asarray([13, 21], jnp.int32)):
        ref = attend_paged_verify(q, kc, vc, cache, c0s)
        out = ops.paged_verify_attention_quant(
            q, kc, vc, kp, vp, ks, vs, kt_snap, vt_snap, tbl, c0s,
            interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# rollback is exact for ARBITRARY drafts (hypothesis property, with a
# fixed-seed fallback where hypothesis is unavailable)
# ---------------------------------------------------------------------------
def _check_arbitrary_drafts_never_change_tokens(seed, gamma, quant):
    """Substitute RANDOM tokens for the drafter's proposals: the
    accept/reject machinery must still reproduce the plain greedy output
    exactly (random drafts mostly reject, exercising full and partial
    rollback), and allocator refcounts, free-list integrity, table-prefix
    contiguity, and int8 ring consistency hold after every single decode
    step (``_run`` calls ``check_invariants`` per scheduler step)."""
    cfg = get_config("dialogpt-medium").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    stack = (cfg, params)
    plain = _paged(stack, spec=False, quant=quant)
    preqs = _run(plain, [p for p, _ in REQUESTS])

    spec = _paged(stack, spec=True, quant=quant, gamma=gamma)
    draw = np.random.default_rng(seed)

    def noisy(draft):
        # half the rounds: pure noise (full rejection path); half:
        # corrupt a random suffix (partial acceptance + partial rollback)
        noise = draw.integers(0, cfg.vocab_size,
                              size=draft.shape).astype(draft.dtype)
        if draw.integers(0, 2):
            return noise
        cut = int(draw.integers(0, draft.shape[1]))
        out = draft.copy()
        out[:, cut:] = noise[:, cut:]
        return out

    spec._draft_tokens = noisy
    sreqs = _run(spec, [p for p, _ in REQUESTS])
    for rp, rs in zip(preqs, sreqs):
        np.testing.assert_array_equal(rs.result.token_ids,
                                      rp.result.token_ids)
    assert spec.stats["spec_rounds"] > 0


if HAVE_HYPOTHESIS:
    class TestSpecRollbackProperty:
        @given(seed=st.integers(0, 2**31 - 1), gamma=st.sampled_from([2, 4]),
               quant=st.booleans())
        @settings(max_examples=5, deadline=None)
        def test_arbitrary_drafts_never_change_tokens(self, seed, gamma,
                                                      quant):
            _check_arbitrary_drafts_never_change_tokens(seed, gamma, quant)
else:
    @pytest.mark.parametrize("seed,gamma,quant",
                             [(11, 4, False), (12, 2, False), (13, 4, True)])
    def test_spec_rollback_fixed_seeds(seed, gamma, quant):
        _check_arbitrary_drafts_never_change_tokens(seed, gamma, quant)
