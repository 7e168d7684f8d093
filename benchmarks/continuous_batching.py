# Continuous batching vs serial FIFO: tokens/s on a mixed workload.
"""Throughput benchmark for the slot-pool and paged-pool decode engines.

  PYTHONPATH=src python benchmarks/continuous_batching.py
  PYTHONPATH=src python benchmarks/continuous_batching.py --full --max-new 32
  PYTHONPATH=src python benchmarks/continuous_batching.py --smoke   # CI

Workload: a fixed mix of recycled exact-prefix hits, partial-block hits and
cold misses (the three admission modes a production pool sees), served by

  * the serial FIFO scheduler (one generate per request — the seed's path),
  * the continuous-batching dense slot pool at batch sizes {1, 4, 8},
  * the paged block-table pool at the same batch sizes (PR 2) in BOTH
    admission modes: ``paged_staged_b*`` (the original staging-cache
    round-trip) and ``paged_chunked_b*`` (PR 5's paged-native chunked
    prefill — the default admission route),
  * with ``--int8``, the int8 paged pool (PR 4) in both modes
    (``paged_int8_staged_b*`` / ``paged_int8_chunked_b*``) plus
    ``int8_vs_fp_b*`` summaries (bytes-in-use reduction, tokens/s, max
    resident blocks).

All paths see identical precached recycler contents.  Each configuration
runs the workload once untimed (jit warmup — per-suffix-length prefill
executables on the staged paths, ONE chunk executable on the chunked
paths, plus the one pool decode executable) and twice timed (best wins;
the box is shared).  Reported tokens/s counts generated tokens only.

Paged rows also record the admission-latency story this PR is about:
``ttft_mean_s`` / ``ttft_max_s`` (submit -> first sampled token, the
serving TTFT) and ``prefill_compiles`` (compiled prefill executables —
per distinct suffix length on the staged path, exactly 1 on the chunked
path), summarized per batch size in ``chunked_vs_staged_b*`` rows.

With ``--semantic``, a prefix-free workload (every prompt shares 8
interior blocks with one donor but no prefix) runs with semantic
block-donor grafting off and on: ``semantic_off_b*`` / ``semantic_on_b*``
rows record hit rate, reuse depth, graft/refusal counts and gate
divergence, ``semantic_vs_exact_b*`` summarizes reuse-where-prefix-sees-
zero plus output fidelity (embedding cosine, on vs off), and
``semantic_preservation`` proves the standard workload's prefix-path
requests keep their mode and text under semantic mode.

With ``--speculative``, self-speculative decode (the same weights draft
``--gamma`` tokens against a pre-gathered sink+recent block view via
fixed-point sweeps — one multi-token dispatch per sweep — and ONE
batched dispatch verifies the bundle) runs against plain chunked decode
on a LONG-generation workload (``--long-new`` tokens per request — the
regime where decode dominates): ``{label}_spec_long_b*`` vs
``{label}_chunked_long_b*`` rows record decode tok/s, TPOT p50/p95,
acceptance rate, mean accepted length and tokens per round, summarized
in ``spec_vs_plain_{label}_b*`` with the decode speedup.  Every timed
row now carries ``tpot_p50_s`` / ``tpot_p95_s`` (per-token decode
latency; a speculative burst records equal per-token shares of its
round, so accepted drafts show up as lower TPOT).

Besides the table, the run writes ``BENCH_continuous_batching.json`` (or
``--json-out PATH``) so CI can track the perf trajectory machine-readably.
``--check-chunked`` (CI smoke) fails the run if any chunked config
compiled more than one prefill executable per chunk shape or if the
TTFT rows are missing from the artifact; ``--packed`` adds a
burst-arrival workload (8 requests at once) served by the chunked route
vs the ragged packed route (ALL pending admissions' chunk steps in ONE
dispatch per engine step), with ``packed_vs_chunked_b*`` rows recording
admission tokens/s, TTFT p50/p95 and dispatch/executable counts, and
``--check-packed`` gates token identity, one-dispatch-per-step, the
bucket-ladder compile bound and TTFT p95 no worse than chunked; ``--check-semantic`` fails it
unless the semantic rows show grafted reuse depth > 0 where the prefix
paths report 0, with the prefix paths byte-preserved; ``--check-spec``
fails it unless speculative rounds actually ran AND speculative greedy
decode is token-identical to non-speculative greedy decode (the
equivalence oracle — perf is reported, correctness is gated).
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_config
from repro.core import HashEmbedder
from repro.core.metrics import tpot_summary
from repro.models import init_params, paged_block_bytes
from repro.models.cache import cache_bytes
from repro.runtime import enable_compile_cache
from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                           Engine, FIFOScheduler, PagedEngine)

CACHED = [
    "the quick brown fox jumps over the lazy dog today",
    "what is the capital of france and why",
    "explain machine learning in simple terms please",
]

# 64 shared characters = 8 aligned byte-token blocks at block_size 8; a
# 7-char head (+BOS) fills exactly one differing block, so every query
# shares 8 interior blocks with the donor while sharing NO prefix — the
# workload where both prefix paths report zero reuse
SEM_MID = "the quick brown fox jumps over the lazy dog again and again!!!!"
SEM_DONOR = "aaaaaaa" + SEM_MID


def workload(n_requests: int):
    """Round-robin mix: exact hit / partial hit / cold miss."""
    reqs = []
    for i in range(n_requests):
        kind = i % 3
        if kind == 0:
            reqs.append(CACHED[i % len(CACHED)] + f" extended {i}")
        elif kind == 1:
            base = CACHED[i % len(CACHED)].rsplit(" ", 2)[0]
            reqs.append(base + f" divergent tail {i}")
        else:
            reqs.append(f"cold unseen prompt number {i} with no overlap")
    return reqs


def semantic_workload(n_requests: int):
    """Prefix-free queries sharing the donor's middle blocks."""
    return [f"q{i:06d}" + SEM_MID for i in range(n_requests)]


def _run(sched, prompts, max_new):
    """(seconds, generated_tokens, ttfts, served_results) for one
    workload pass.  Run twice on the SAME scheduler: the first pass
    compiles every prefill executable (one per suffix length staged, one
    total chunked) plus the pool decode step; only the second pass is a
    fair timing (the paper's T4 runs have no compile step either)."""
    sched.completed = []
    for p in prompts:
        sched.submit(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    rejected = [r for r in done if r.result is None]
    if rejected:
        print(f"# {len(rejected)} request(s) rejected: {rejected[0].error}")
    served = [r.result for r in done if r.result is not None]
    toks = sum(r.gen_tokens for r in served)
    ttfts = [r.ttft_s for r in served]
    return dt, toks, ttfts, served


def timed_best(sched, prompts, max_new):
    """Warmup pass, then best of two timed passes (this box is shared;
    a single pass can eat a CPU-contention spike).  The warmup pass's
    TTFTs are returned too (as the 5th element): they INCLUDE compile
    time, which is the cold-start story — the staged admission path
    compiles one prefill executable per distinct suffix length right
    there, the chunked path compiles once ever.  The winning pass's
    GenResults ride along (4th element) so callers can summarize TPOT
    over single-pass per-token timings."""
    _, _, cold, _ = _run(sched, prompts, max_new)      # warmup compile
    a = _run(sched, prompts, max_new)
    b = _run(sched, prompts, max_new)
    return min(a, b, key=lambda r: r[0]) + (cold,)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI preset: fewer requests/batches, reduced "
                         "config, still emits the JSON record")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--int8", action="store_true",
                    help="also run the int8 paged pool (kv_quant) and "
                         "record fp-vs-int8 device_kv_bytes_in_use, "
                         "tokens/s and max resident blocks")
    ap.add_argument("--semantic", action="store_true",
                    help="also run the semantic block-donor workload "
                         "(prefix-free prompts sharing interior blocks "
                         "with one donor) with grafting off and on, and "
                         "record hit rate / reuse depth / graft counts / "
                         "gate divergence / output fidelity rows")
    ap.add_argument("--check-semantic", action="store_true",
                    help="fail (exit 1) unless the semantic-on rows show "
                         "grafts with reuse depth > 0 where the prefix "
                         "paths report 0, output fidelity clears "
                         "--fidelity-min, and semantic mode preserved "
                         "every prefix-path request's mode and text "
                         "(CI gate; implies --semantic)")
    ap.add_argument("--fidelity-min", type=float, default=-1.0,
                    help="minimum mean embedding cosine between "
                         "semantic-on and semantic-off outputs for "
                         "--check-semantic (default -1.0 = record only; "
                         "raise it when running trained weights, where "
                         "boundary recompute should keep outputs close)")
    ap.add_argument("--speculative", action="store_true",
                    help="also run self-speculative decode (sparse-view "
                         "drafter + single-dispatch verify) against plain "
                         "chunked decode on a LONG-generation workload "
                         "(--long-new tokens per request) and record "
                         "decode tok/s, TPOT p50/p95, acceptance rate "
                         "and mean accepted length per config")
    ap.add_argument("--gamma", type=int, default=12,
                    help="draft depth per speculative round (int8 pools "
                         "cap it at (fp_tail_blocks-1)*block_size)")
    ap.add_argument("--long-new", type=int, default=128,
                    help="generated tokens per request on the "
                         "long-generation speculative workload "
                         "(--smoke caps it at 16)")
    ap.add_argument("--check-spec", action="store_true",
                    help="fail (exit 1) unless speculative rows exist "
                         "with spec_rounds > 0 AND speculative greedy "
                         "decode is token-identical to non-speculative "
                         "greedy decode on the standard workload "
                         "(CI gate; implies --speculative)")
    ap.add_argument("--check-chunked", action="store_true",
                    help="fail (exit 1) unless every chunked config "
                         "compiled at most one prefill executable per "
                         "fixed chunk shape and every paged row carries "
                         "TTFT data (CI gate)")
    ap.add_argument("--packed", action="store_true",
                    help="also run the burst-arrival workload (8 requests "
                         "submitted at once) through the packed admission "
                         "route vs the chunked one and record admission "
                         "tokens/s, TTFT p50/p95 and prefill dispatch/"
                         "executable counts (packed_vs_chunked_b* rows)")
    ap.add_argument("--check-packed", action="store_true",
                    help="fail (exit 1) unless packed greedy decode is "
                         "token-identical to chunked on the burst "
                         "workload, the packed route issued ONE prefill "
                         "dispatch per engine step, compiled at most one "
                         "executable per packed bucket, and its TTFT p95 "
                         "is no worse than chunked (CI gate; implies "
                         "--packed)")
    ap.add_argument("--mesh", default=None, metavar="DxT",
                    help="also run the mesh-sharded ShardedServer (D "
                         "data-parallel PagedEngine replicas x T-way TP "
                         "block pools over a shared host L2) at replicas "
                         "in {1, D} and record tokens/s, per-replica "
                         "device KV bytes in use and cross-replica "
                         "warm-admission promotions; needs D*T devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count set BEFORE jax imports)")
    ap.add_argument("--check-mesh", action="store_true",
                    help="fail (exit 1) unless the sharded rows are "
                         "token-identical to the single-device paged "
                         "engine, DP scaling at D replicas beats 1 "
                         "replica, and the warm cross-replica pass "
                         "promoted blocks instead of recomputing (CI "
                         "gate; implies --mesh 2x2)")
    ap.add_argument("--overload", action="store_true",
                    help="also run the overload workload: the standard "
                         "request mix against an UNDERSIZED overcommitted "
                         "block pool (forced preemptions with exact "
                         "resume) plus a bounded-queue pass (typed "
                         "sheds), recording preemption/requeue/shed "
                         "counts, recomputed tokens and the pressure "
                         "slo_summary rows")
    ap.add_argument("--check-preempt", action="store_true",
                    help="fail (exit 1) unless every non-shed overload "
                         "request completes token-identical to the "
                         "uncapped run, at least one preemption actually "
                         "fired, pool invariants hold after the run, and "
                         "shed requests carry typed outcomes (CI gate; "
                         "implies --overload)")
    ap.add_argument("--json-out", default="BENCH_continuous_batching.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.requests, args.max_new, args.batches = 6, 4, [4]

    cfg = get_config("dialogpt-medium")
    if not args.full:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = workload(args.requests)

    eng = Engine(cfg, params, max_new_tokens=args.max_new, block_size=8,
                 enable_partial=True)
    eng.precache(CACHED)
    serial_sched = FIFOScheduler(eng)

    rows = []
    dt, toks, _, served, _ = timed_best(serial_sched, prompts, args.max_new)
    serial_tps = toks / dt
    tp = tpot_summary(served)
    rows.append({"config": "serial_fifo", "wall_s": dt, "gen_tokens": toks,
                 "tokens_per_s": serial_tps, "speedup": 1.0,
                 "tpot_p50_s": tp["tpot_p50_s"],
                 "tpot_p95_s": tp["tpot_p95_s"]})

    for b in args.batches:
        beng = BatchedEngine(cfg, params, max_batch=b,
                             capacity=args.capacity,
                             max_new_tokens=args.max_new, block_size=8,
                             enable_partial=True)
        beng.precache(CACHED)
        dt, toks, _, served, _ = timed_best(ContinuousBatchingScheduler(beng),
                                            prompts, args.max_new)
        tp = tpot_summary(served)
        rows.append({"config": f"dense_pool_b{b}", "wall_s": dt,
                     "gen_tokens": toks, "tokens_per_s": toks / dt,
                     "speedup": (toks / dt) / serial_tps,
                     "tpot_p50_s": tp["tpot_p50_s"],
                     "tpot_p95_s": tp["tpot_p95_s"],
                     "device_kv_bytes": cache_bytes(beng.pool)})

    paged_variants = [(False, "paged")]
    if args.int8:
        paged_variants.append((True, "paged_int8"))
    for quant, label in paged_variants:
        for mode in ("staged", "chunked"):
            for b in args.batches:
                peng = PagedEngine(cfg, params, max_batch=b,
                                   capacity=args.capacity,
                                   max_new_tokens=args.max_new,
                                   block_size=8, enable_partial=True,
                                   kv_quant=quant, prefill_mode=mode)
                peng.precache(CACHED)
                dt, toks, ttfts, served, cold = timed_best(
                    ContinuousBatchingScheduler(peng), prompts,
                    args.max_new)
                blk_bytes = paged_block_bytes(cfg, peng.block, quant=quant)
                tp = tpot_summary(served)
                rows.append({
                    "config": f"{label}_{mode}_b{b}", "wall_s": dt,
                    "gen_tokens": toks, "tokens_per_s": toks / dt,
                    "speedup": (toks / dt) / serial_tps,
                    "tpot_p50_s": tp["tpot_p50_s"],
                    "tpot_p95_s": tp["tpot_p95_s"],
                    # admission latency: submit -> first sampled token
                    "ttft_mean_s": sum(ttfts) / max(len(ttfts), 1),
                    "ttft_max_s": max(ttfts, default=0.0),
                    # cold = warmup pass, compiles included: the
                    # per-suffix-length recompile cost the chunked
                    # route eliminates
                    "ttft_cold_mean_s": sum(cold) / max(len(cold), 1),
                    "ttft_cold_max_s": max(cold, default=0.0),
                    # compiled prefill executables: per suffix length on
                    # the staged path, exactly one on the chunked path
                    "prefill_compiles": peng.prefill_compiles(),
                    "prefill_chunk_shapes": len(peng.chunk_shapes),
                    "prefill_chunks": peng.stats["prefill_chunks"],
                    "staging_prefills": peng.stats["staging_prefills"],
                    "spec_preallocs": peng.stats["spec_preallocs"],
                    "layout_conversions":
                        peng.stats["layout_conversions"],
                    # device_kv_bytes is the STATIC allocation in both
                    # pool rows (apples to apples with dense_pool_b*);
                    # the peak/in-use numbers show what sharing and
                    # on-demand allocation actually touched
                    "device_kv_bytes": cache_bytes(peng.pool),
                    "device_kv_bytes_peak":
                        peng.allocator.stats["peak_live"] * blk_bytes,
                    "device_kv_bytes_in_use":
                        peng.device_kv_bytes_in_use(),
                    "max_resident_blocks":
                        peng.allocator.stats["peak_live"],
                    "resident_hits": peng.stats["resident_hits"],
                    "host_promotions": peng.stats["host_promotions"],
                    "h2d_bytes": peng.stats["h2d_bytes"],
                    "cow_copies": peng.stats["cow_copies"]})

    by = {r["config"]: r for r in rows}
    for quant, label in paged_variants:
        # staged-vs-chunked admission summary per batch size: TTFT and
        # compile counts are what the chunked route exists to improve
        for b in args.batches:
            s = by[f"{label}_staged_b{b}"]
            c = by[f"{label}_chunked_b{b}"]
            rows.append({
                "config": f"chunked_vs_staged_{label}_b{b}",
                "ttft_mean_staged_s": s["ttft_mean_s"],
                "ttft_mean_chunked_s": c["ttft_mean_s"],
                "ttft_speedup": s["ttft_mean_s"] / max(c["ttft_mean_s"],
                                                       1e-9),
                "ttft_cold_mean_staged_s": s["ttft_cold_mean_s"],
                "ttft_cold_mean_chunked_s": c["ttft_cold_mean_s"],
                "ttft_cold_speedup":
                    s["ttft_cold_mean_s"] / max(c["ttft_cold_mean_s"],
                                                1e-9),
                "prefill_compiles_staged": s["prefill_compiles"],
                "prefill_compiles_chunked": c["prefill_compiles"],
                "tokens_per_s_staged": s["tokens_per_s"],
                "tokens_per_s_chunked": c["tokens_per_s"],
            })

    if args.int8:
        # machine-readable fp-vs-int8 summary per batch size (over the
        # default chunked admission route): the whole point of the int8
        # tier is more resident context per HBM byte
        for b in args.batches:
            fp, q8 = by[f"paged_chunked_b{b}"], by[f"paged_int8_chunked_b{b}"]
            rows.append({
                "config": f"int8_vs_fp_b{b}",
                "bytes_in_use_fp": fp["device_kv_bytes_in_use"],
                "bytes_in_use_int8": q8["device_kv_bytes_in_use"],
                "bytes_reduction":
                    fp["device_kv_bytes_in_use"]
                    / max(q8["device_kv_bytes_in_use"], 1),
                "tokens_per_s_fp": fp["tokens_per_s"],
                "tokens_per_s_int8": q8["tokens_per_s"],
                "max_resident_blocks_fp": fp["max_resident_blocks"],
                "max_resident_blocks_int8": q8["max_resident_blocks"],
            })

    if args.check_packed:
        args.packed = True
    if args.packed:
        # Burst arrival — the regime the packed route exists for: 8
        # requests land at once, so every engine step has up to 8
        # admissions mid-flight.  The chunked route advances them with
        # one prefill dispatch EACH per step; the packed route lands all
        # their chunk steps in ONE ragged packed dispatch.  Same
        # workload, same precache, greedy — tokens must be identical,
        # the win is admission latency (TTFT) and dispatch count.
        burst_b = 8
        burst_prompts = workload(burst_b)
        pair = {}
        for mode in ("chunked", "packed"):
            peng = PagedEngine(cfg, params, max_batch=burst_b,
                               capacity=args.capacity,
                               max_new_tokens=args.max_new, block_size=8,
                               enable_partial=True, prefill_mode=mode)
            peng.precache(CACHED)
            sched = ContinuousBatchingScheduler(peng)
            dt, toks, ttfts, served, cold = timed_best(
                sched, burst_prompts, args.max_new)
            peng.check_invariants()
            from repro.core.metrics import slo_summary
            slo = slo_summary(served)
            prompt_toks = sum(r.prompt_tokens for r in served)
            row = {
                "config": f"{mode}_burst_b{burst_b}", "wall_s": dt,
                "gen_tokens": toks, "tokens_per_s": toks / dt,
                "speedup": (toks / dt) / serial_tps,
                # admission throughput: prompt tokens prefilled per
                # wall-second of the whole burst pass
                "admission_tokens_per_s": prompt_toks / dt,
                "ttft_mean_s": sum(ttfts) / max(len(ttfts), 1),
                "ttft_p50_s": slo["ttft_p50_s"],
                "ttft_p95_s": slo["ttft_p95_s"],
                "tpot_p50_s": slo["tpot_p50_s"],
                "tpot_p95_s": slo["tpot_p95_s"],
                "prefill_compiles": peng.prefill_compiles(),
                "prefill_chunks": peng.stats["prefill_chunks"],
                "prefill_dispatches": peng.stats["prefill_dispatches"],
                "prefill_packed_steps":
                    peng.stats.get("prefill_packed_steps", 0),
                "packed_buckets": len(peng.packed_buckets),
                # greedy outputs are deterministic across the timed
                # passes, so the scheduler's final pass stands in for
                # the best one in the identity gate
                "tokens_by_prompt": {r.prompt: [int(t) for t in
                                                r.result.token_ids]
                                     for r in sched.completed
                                     if r.result is not None},
            }
            pair[mode] = row
            rows.append(row)
        c, p = pair["chunked"], pair["packed"]
        rows.append({
            "config": f"packed_vs_chunked_b{burst_b}",
            "admission_tokens_per_s_chunked": c["admission_tokens_per_s"],
            "admission_tokens_per_s_packed": p["admission_tokens_per_s"],
            "ttft_p50_chunked_s": c["ttft_p50_s"],
            "ttft_p50_packed_s": p["ttft_p50_s"],
            "ttft_p95_chunked_s": c["ttft_p95_s"],
            "ttft_p95_packed_s": p["ttft_p95_s"],
            "ttft_p95_speedup": (c["ttft_p95_s"]
                                 / max(p["ttft_p95_s"], 1e-9)),
            "prefill_dispatches_chunked": c["prefill_dispatches"],
            "prefill_dispatches_packed": p["prefill_dispatches"],
            "prefill_compiles_chunked": c["prefill_compiles"],
            "prefill_compiles_packed": p["prefill_compiles"],
            "tokens_identical": (c["tokens_by_prompt"]
                                 == p["tokens_by_prompt"]),
        })
        for row in (c, p):              # the per-prompt tokens served
            del row["tokens_by_prompt"]  # their gate; keep the json lean

    if args.check_spec:
        args.speculative = True
    if args.speculative:
        # Self-speculative decode vs plain chunked decode on a LONG
        # generation workload — the regime speculation targets: decode
        # steps dominate and every accepted draft saves one full-table
        # dispatch.  The short workload above stays untouched as the
        # regression baseline.  int8 pools cap gamma at the ring-restore
        # bound (fp_tail_blocks - 1) * block_size.
        long_new = min(args.long_new, 16) if args.smoke else args.long_new
        cap_long = max(args.capacity,
                       8 * ((96 + long_new) // 8 + 2))
        for quant, label in paged_variants:
            gamma = min(args.gamma, 8) if quant else args.gamma
            for b in args.batches:
                pair = {}
                for spec in (False, True):
                    # full-coverage draft view: with random-init weights
                    # attention is diffuse, so a truly sparse view's
                    # greedy argmax rarely matches the full-context
                    # target (acceptance ~15%).  The view mechanism is
                    # identical either way — gathered once per round,
                    # stale within it — and recent_blocks is the honest
                    # knob a trained checkpoint would shrink.
                    peng = PagedEngine(cfg, params, max_batch=b,
                                       capacity=cap_long,
                                       max_new_tokens=long_new,
                                       block_size=8, enable_partial=True,
                                       kv_quant=quant,
                                       prefill_mode="chunked",
                                       speculative=spec, gamma=gamma,
                                       recent_blocks=cap_long // 8)
                    peng.precache(CACHED)
                    dt, toks, ttfts, served, _ = timed_best(
                        ContinuousBatchingScheduler(peng), prompts,
                        long_new)
                    peng.check_invariants()
                    tp = tpot_summary(served)
                    tag = "spec" if spec else "chunked"
                    row = {
                        "config": f"{label}_{tag}_long_b{b}", "wall_s": dt,
                        "gen_tokens": toks, "tokens_per_s": toks / dt,
                        "speedup": (toks / dt) / serial_tps,
                        "tpot_p50_s": tp["tpot_p50_s"],
                        "tpot_p95_s": tp["tpot_p95_s"],
                        "ttft_mean_s": sum(ttfts) / max(len(ttfts), 1),
                    }
                    if spec:
                        st = peng.stats
                        row.update({
                            "gamma": gamma,
                            "spec_iters": peng.spec_iters,
                            "recent_blocks": peng.recent_blocks,
                            "spec_rounds": st["spec_rounds"],
                            "acceptance_rate":
                                st["spec_accepted_tokens"]
                                / max(st["spec_draft_tokens"], 1),
                            "mean_accepted_len":
                                st["spec_accepted_tokens"]
                                / max(st["spec_rounds"], 1),
                            "tokens_per_round":
                                st["spec_emitted_tokens"]
                                / max(st["spec_rounds"], 1),
                            "spec_fallback_steps":
                                st["spec_fallback_steps"],
                        })
                    pair[spec] = row
                    rows.append(row)
                rows.append({
                    "config": f"spec_vs_plain_{label}_b{b}",
                    "tokens_per_s_plain": pair[False]["tokens_per_s"],
                    "tokens_per_s_spec": pair[True]["tokens_per_s"],
                    "decode_speedup": (pair[True]["tokens_per_s"]
                                       / max(pair[False]["tokens_per_s"],
                                             1e-9)),
                    "tpot_p50_plain_s": pair[False]["tpot_p50_s"],
                    "tpot_p50_spec_s": pair[True]["tpot_p50_s"],
                    "acceptance_rate": pair[True]["acceptance_rate"],
                    "mean_accepted_len": pair[True]["mean_accepted_len"],
                    "tokens_per_round": pair[True]["tokens_per_round"],
                })

    if args.check_semantic:
        args.semantic = True
    if args.semantic:
        # Semantic block-donor recycling (grafting rides the chunked
        # admission only).  graft_max_div is wide open here: with random
        # init (this benchmark never loads trained weights) the boundary
        # recompute always diverges numerically from the donor, and the
        # point of these rows is the reuse/fidelity ACCOUNTING — the
        # gate's recorded divergences, not its policy.
        sem_prompts = semantic_workload(args.requests)
        emb = HashEmbedder()
        texts = {}
        for sem in (False, True):
            label = "on" if sem else "off"
            for b in args.batches:
                peng = PagedEngine(cfg, params, max_batch=b,
                                   capacity=args.capacity,
                                   max_new_tokens=args.max_new,
                                   block_size=8, enable_partial=True,
                                   prefill_mode="chunked", semantic=sem,
                                   graft_max_div=1e9)
                sched = ContinuousBatchingScheduler(peng)
                sched.submit(SEM_DONOR, admit=True,
                             max_new_tokens=args.max_new)
                sched.run()
                sched.completed = []
                for p in sem_prompts:
                    sched.submit(p, max_new_tokens=args.max_new)
                t0 = time.perf_counter()
                done = sched.run()
                dt = time.perf_counter() - t0
                peng.check_invariants()
                served = {r.prompt: r.result for r in done
                          if r.result is not None}
                texts[(sem, b)] = {p: served[p].text for p in sem_prompts
                                   if p in served}
                depths = [served[p].reuse_depth for p in sem_prompts
                          if p in served]
                toks = sum(r.gen_tokens for r in served.values())
                divs = peng.semantic_gate_divs
                rows.append({
                    "config": f"semantic_{label}_b{b}", "wall_s": dt,
                    "gen_tokens": toks, "tokens_per_s": toks / dt,
                    "speedup": (toks / dt) / serial_tps,
                    "hit_rate": (sum(served[p].cache_hit
                                     for p in served) / max(len(served),
                                                            1)),
                    "reuse_depth_mean": (sum(depths)
                                         / max(len(depths), 1)),
                    "reuse_depth_max": max(depths, default=0),
                    "semantic_grafts": peng.stats["semantic_grafts"],
                    "semantic_refusals":
                        peng.stats["semantic_refusals"],
                    "semantic_resident_grafts":
                        peng.stats["semantic_resident_grafts"],
                    "semantic_host_grafts":
                        peng.stats["semantic_host_grafts"],
                    "tokens_grafted": peng.stats["tokens_grafted"],
                    "gate_div_mean": (sum(divs) / max(len(divs), 1)),
                    "gate_div_max": max(divs, default=0.0)})
        by = {r["config"]: r for r in rows}
        for b in args.batches:
            off, on = by[f"semantic_off_b{b}"], by[f"semantic_on_b{b}"]
            # fidelity: mean embedding cosine of per-request outputs, on
            # vs off — 1.0 means grafting changed nothing the embedder
            # can see (only meaningful with trained weights)
            cos = [float(emb.encode(texts[(True, b)][p])
                         @ emb.encode(texts[(False, b)][p]))
                   for p in sem_prompts
                   if p in texts[(True, b)] and p in texts[(False, b)]]
            rows.append({
                "config": f"semantic_vs_exact_b{b}",
                "reuse_depth_mean_off": off["reuse_depth_mean"],
                "reuse_depth_mean_on": on["reuse_depth_mean"],
                "hit_rate_off": off["hit_rate"],
                "hit_rate_on": on["hit_rate"],
                "semantic_grafts": on["semantic_grafts"],
                "tokens_grafted": on["tokens_grafted"],
                "gate_div_mean": on["gate_div_mean"],
                "fidelity": sum(cos) / max(len(cos), 1)})
        # prefix-path preservation: on the STANDARD workload semantic
        # mode must not change any request's mode or text (grafting only
        # ever fires on a prefix miss)
        pres_results = []
        for sem in (False, True):
            peng = PagedEngine(cfg, params, max_batch=args.batches[-1],
                               capacity=args.capacity,
                               max_new_tokens=args.max_new, block_size=8,
                               enable_partial=True,
                               prefill_mode="chunked", semantic=sem)
            peng.precache(CACHED)
            sched = ContinuousBatchingScheduler(peng)
            for p in prompts:
                sched.submit(p, max_new_tokens=args.max_new)
            done = sched.run()
            pres_results.append({r.prompt: r.result for r in done
                                 if r.result is not None})
        off_r, on_r = pres_results
        mismatches = [p for p in prompts
                      if p in off_r and off_r[p].cache_hit
                      and (p not in on_r
                           or on_r[p].mode != off_r[p].mode
                           or on_r[p].text != off_r[p].text)]
        rows.append({"config": "semantic_preservation",
                     "prefix_hits_checked":
                         sum(1 for p in prompts
                             if p in off_r and off_r[p].cache_hit),
                     "mismatches": len(mismatches),
                     "preserved": not mismatches})

    if args.check_mesh and args.mesh is None:
        args.mesh = "2x2"
    if args.mesh is not None:
        # Mesh-sharded serving (PR 8): D data-parallel PagedEngine
        # replicas, each TP-sharding its block pool over a (1, T)
        # sub-mesh, sharing ONE host L2.  Three claims measured here:
        # (1) greedy tokens stay identical to the single-device paged
        # engine, (2) a prefix admitted on replica 0 serves on the last
        # replica as block-granular host promotions (cross-replica warm
        # admission, zero recompute), (3) the DP layout beats the
        # device-count-equivalent pure-TP layout in tokens/s.
        #
        # The scaling comparison holds the HARDWARE constant: the same
        # D*T devices and the same aggregate block budget laid out as
        # ONE replica TP-sharded D*T ways (mesh_1x{D*T}) or as D
        # replicas TP-sharded T ways (mesh_{D}x{T}).  That is the
        # deployment question data parallelism answers — wider TP buys
        # narrower per-shard work plus a wider partial-softmax
        # reduction on EVERY dispatch, while DP replicas keep the
        # collective narrow and split the request stream.  On the
        # forced-host-device smoke topology all shards share the same
        # cores, so the margin is pure dispatch/collective overhead; on
        # a real mesh the replicas additionally overlap on disjoint
        # devices.
        import gc
        import statistics
        from repro.launch.serve import ShardedServer
        dp, tp = (int(x) for x in args.mesh.lower().split("x"))
        b = args.batches[-1]
        mesh_prompts = workload(2 * dp * b)
        # decode-dominated regime: the replication story is about decode
        # throughput, and a 4-token smoke generation would be all
        # admission overhead
        mesh_new = max(args.max_new, 16)
        nbt = args.capacity // 8
        replica_default = b * nbt + nbt + 1      # the engine default

        ref = PagedEngine(cfg, params, max_batch=b, capacity=args.capacity,
                          max_new_tokens=mesh_new, block_size=8,
                          enable_partial=True, prefill_mode="chunked")
        ref.precache(CACHED)
        sched = ContinuousBatchingScheduler(ref)
        for p in mesh_prompts:
            sched.submit(p, max_new_tokens=mesh_new)
        done = sched.run()
        ref_texts = {r.prompt: r.result.text for r in done
                     if r.result is not None}

        pair = {}
        for nrep, tp_c in ((1, dp * tp), (dp, tp)):
            # equal aggregate blocks: the wide-TP baseline holds D
            # replicas' worth of pool over its wider shard
            srv = ShardedServer(cfg, params, replicas=nrep, tp=tp_c,
                                max_batch=b, capacity=args.capacity,
                                num_blocks=(dp // nrep) * replica_default,
                                max_new_tokens=mesh_new, block_size=8,
                                enable_partial=True,
                                prefill_mode="chunked")
            srv.run(CACHED, replica=0, admit=True)
            # warm cross-replica pass: entries admitted on replica 0 must
            # serve on the LAST replica via host promotions (with one
            # replica this is a plain resident re-serve, promotions 0)
            srv.run(CACHED, replica=nrep - 1,
                    max_new_tokens=mesh_new)
            warm_promos = srv.shared_stats["cross_replica_promotions"]

            def once():
                gc.collect()
                gc.disable()          # a mid-pass GC pause swamps the
                try:                  # margin on a single-core box
                    t0 = time.perf_counter()
                    res = srv.run(mesh_prompts, max_new_tokens=mesh_new)
                    dt = time.perf_counter() - t0
                finally:
                    gc.enable()
                return dt, sum(r.gen_tokens for r in res), res
            once()                                  # warmup compile
            passes = [once() for _ in range(5)]
            dt, toks, res = sorted(passes,
                                   key=lambda r: r[0])[len(passes) // 2]
            srv.check_invariants()
            identical = all(r.text == ref_texts[p]
                            for p, r in zip(mesh_prompts, res))
            st = srv.stats()
            row = {
                "config": f"mesh_{nrep}x{tp_c}_b{b}", "wall_s": dt,
                "gen_tokens": toks, "tokens_per_s": toks / dt,
                "speedup": (toks / dt) / serial_tps,
                "replicas": nrep, "tp": tp_c,
                "tokens_identical": identical,
                "cross_replica_promotions":
                    st["cross_replica_promotions"],
                "warm_cross_replica_promotions": warm_promos,
                "host_promotions_last_replica":
                    st["per_replica"][-1]["stats"]["host_promotions"],
                "device_kv_bytes_in_use_per_replica":
                    [p["device_kv_bytes_in_use"]
                     for p in st["per_replica"]],
                "device_kv_bytes_per_device":
                    [p["device_kv_bytes_per_device"]
                     for p in st["per_replica"]],
                "kv_tp_degree": st["per_replica"][0]["kv_tp_degree"],
            }
            pair[nrep] = row
            rows.append(row)
        if dp > 1:
            r1, rn = pair[1], pair[dp]
            rows.append({
                "config": f"mesh_dp_scaling_{dp}x{tp}_b{b}",
                "tokens_per_s_r1": r1["tokens_per_s"],
                "tokens_per_s_rN": rn["tokens_per_s"],
                "dp_scaling": rn["tokens_per_s"]
                    / max(r1["tokens_per_s"], 1e-9),
                "warm_cross_replica_promotions":
                    rn["warm_cross_replica_promotions"],
                "tokens_identical": r1["tokens_identical"]
                    and rn["tokens_identical"],
            })

    if args.overload or args.check_preempt:
        # Overload workload: same request mix, but the paged pool is
        # deliberately undersized so decode writes and admission chunks
        # run out of blocks mid-flight.  The pressure-safe claim under
        # test: the engine preempts victims (demote to host L2, requeue,
        # exact resume) instead of failing the step, so every request
        # still completes with tokens identical to the uncapped run —
        # the only cost is recomputed tokens and extra wall time.  A
        # second pass bounds the queue to prove sheds are typed data,
        # not exceptions.
        from repro.core.metrics import slo_summary
        from repro.serving.scheduler import RequestOutcome
        b = args.batches[-1]
        over_prompts = workload(min(args.requests, 6))
        # blocks for roughly ONE full row (capacity/8 at block_size 8):
        # b concurrent rows cannot all be resident, and completed-row
        # frees plus trie eviction cannot hide the pressure
        overload_blocks = max(10, args.capacity // 8)

        def _overload_run(num_blocks):
            eng_kw = {}
            if num_blocks is not None:
                eng_kw = {"num_blocks": num_blocks, "overcommit": True}
            peng = PagedEngine(cfg, params, max_batch=b,
                               capacity=args.capacity,
                               max_new_tokens=args.max_new, block_size=8,
                               enable_partial=True, prefill_mode="chunked",
                               **eng_kw)
            peng.precache(CACHED)
            sched = ContinuousBatchingScheduler(peng)
            reqs = [sched.submit(p, max_new_tokens=args.max_new)
                    for p in over_prompts]
            t0 = time.perf_counter()
            sched.run()
            dt = time.perf_counter() - t0
            return peng, sched, reqs, dt

        ref_eng, _, ref_reqs, _ = _overload_run(None)
        ref_text = {p: r.result.text for p, r in zip(over_prompts,
                                                     ref_reqs)}
        peng, sched, reqs, dt = _overload_run(overload_blocks)
        invariants_ok = True
        try:
            peng.check_invariants()
        except AssertionError:
            invariants_ok = False
        mismatched = [p for p, r in zip(over_prompts, reqs)
                      if r.outcome != RequestOutcome.OK
                      or r.result.text != ref_text[p]]
        served = [r.result for r in reqs if r.result is not None]
        slo = slo_summary(served, reqs)
        rows.append({
            "config": f"overload_b{b}",
            "wall_s": dt,
            "gen_tokens": sum(r.gen_tokens for r in served),
            "tokens_per_s": sum(r.gen_tokens for r in served) / dt,
            "speedup": (sum(r.gen_tokens for r in served) / dt)
                / serial_tps,
            "num_blocks": overload_blocks,
            "preemptions": peng.stats["preemptions"],
            "preempt_errors": peng.stats["preempt_errors"],
            "step_rollbacks": peng.stats["step_rollbacks"],
            "tokens_recomputed":
                peng.stats["preempted_tokens_recomputed"],
            "requeues": sched.stats["preemptions"],
            "admissions_deferred": sched.stats["admissions_deferred"],
            "preemption_rate": slo["preemption_rate"],
            "tokens_identical": not mismatched,
            "invariants_ok": invariants_ok,
        })

        # bounded-queue pass: overflow sheds at submit with a typed
        # outcome; accepted requests are untouched by their neighbours'
        # rejection
        qeng = PagedEngine(cfg, params, max_batch=b,
                           capacity=args.capacity,
                           max_new_tokens=args.max_new, block_size=8,
                           enable_partial=True, prefill_mode="chunked")
        qsched = ContinuousBatchingScheduler(qeng, queue_limit=2)
        qreqs = [qsched.submit(p, max_new_tokens=args.max_new)
                 for p in over_prompts]
        qsched.run()
        qserved = [r.result for r in qreqs if r.result is not None]
        qslo = slo_summary(qserved, qreqs)
        untyped = [r for r in qreqs if r.outcome is None]
        rows.append({
            "config": f"overload_shed_b{b}",
            "queue_limit": 2,
            "shed_queue_full": qsched.stats["shed_queue_full"],
            "shed_deadline": qsched.stats["shed_deadline"],
            "shed_rate": qslo["shed_rate"],
            "outcome_counts": qslo["outcome_counts"],
            "all_outcomes_typed": not untyped,
            "accepted_identical": all(
                r.result.text == ref_text[p]
                for p, r in zip(over_prompts, qreqs)
                if r.outcome == RequestOutcome.OK),
        })

    timed = [r for r in rows if "wall_s" in r]
    print(f"{'config':<24} {'wall_s':>8} {'gen_tok':>8} "
          f"{'tok/s':>10} {'speedup':>8} {'tpot_ms':>8} {'ttft_ms':>8} "
          f"{'compiles':>8}")
    for r in timed:
        tpot = (f"{1e3 * r['tpot_p50_s']:>8.2f}"
                if isinstance(r.get("tpot_p50_s"), float)
                else f"{'-':>8}")
        ttft = (f"{1e3 * r['ttft_mean_s']:>8.1f}"
                if "ttft_mean_s" in r else f"{'-':>8}")
        comp = (f"{r['prefill_compiles']:>8d}"
                if "prefill_compiles" in r else f"{'-':>8}")
        print(f"{r['config']:<24} {r['wall_s']:>8.3f} "
              f"{r['gen_tokens']:>8d} {r['tokens_per_s']:>10.1f} "
              f"{r['speedup']:>7.2f}x {tpot} {ttft} {comp}")
    best = max(r["speedup"] for r in timed[1:])
    print(f"\nbest batched speedup over serial: {best:.2f}x")
    for r in rows:
        if r["config"].startswith("chunked_vs_staged"):
            print(f"{r['config']}: warm ttft "
                  f"{1e3 * r['ttft_mean_staged_s']:.1f}ms -> "
                  f"{1e3 * r['ttft_mean_chunked_s']:.1f}ms "
                  f"({r['ttft_speedup']:.2f}x), cold ttft "
                  f"{1e3 * r['ttft_cold_mean_staged_s']:.1f}ms -> "
                  f"{1e3 * r['ttft_cold_mean_chunked_s']:.1f}ms "
                  f"({r['ttft_cold_speedup']:.2f}x), prefill compiles "
                  f"{r['prefill_compiles_staged']} -> "
                  f"{r['prefill_compiles_chunked']}")
        if r["config"].startswith("packed_vs_chunked"):
            print(f"{r['config']}: ttft p95 "
                  f"{1e3 * r['ttft_p95_chunked_s']:.1f}ms -> "
                  f"{1e3 * r['ttft_p95_packed_s']:.1f}ms "
                  f"({r['ttft_p95_speedup']:.2f}x), prefill dispatches "
                  f"{r['prefill_dispatches_chunked']} -> "
                  f"{r['prefill_dispatches_packed']}, compiles "
                  f"{r['prefill_compiles_chunked']} -> "
                  f"{r['prefill_compiles_packed']}, tokens identical: "
                  f"{r['tokens_identical']}")
        if r["config"].startswith("int8_vs_fp"):
            print(f"{r['config']}: {r['bytes_reduction']:.2f}x fewer device "
                  f"KV bytes in use ({r['bytes_in_use_fp']} -> "
                  f"{r['bytes_in_use_int8']})")
        if r["config"].startswith("spec_vs_plain"):
            print(f"{r['config']}: decode "
                  f"{r['tokens_per_s_plain']:.1f} -> "
                  f"{r['tokens_per_s_spec']:.1f} tok/s "
                  f"({r['decode_speedup']:.2f}x), acceptance "
                  f"{100 * r['acceptance_rate']:.0f}%, "
                  f"{r['mean_accepted_len']:.2f} accepted + bonus = "
                  f"{r['tokens_per_round']:.2f} tok/round, tpot p50 "
                  f"{1e3 * r['tpot_p50_plain_s']:.1f}ms -> "
                  f"{1e3 * r['tpot_p50_spec_s']:.1f}ms")
        if r["config"].startswith("semantic_vs_exact"):
            print(f"{r['config']}: reuse depth "
                  f"{r['reuse_depth_mean_off']:.1f} -> "
                  f"{r['reuse_depth_mean_on']:.1f} "
                  f"({r['semantic_grafts']} grafts, "
                  f"{r['tokens_grafted']} tokens), gate div "
                  f"{r['gate_div_mean']:.3f}, fidelity "
                  f"{r['fidelity']:.3f}")
        if r["config"] == "semantic_preservation":
            print(f"semantic_preservation: "
                  f"{r['prefix_hits_checked']} prefix-path hits, "
                  f"{r['mismatches']} mismatches under semantic mode")
        if r["config"].startswith("overload_b"):
            print(f"{r['config']}: num_blocks={r['num_blocks']}, "
                  f"{r['preemptions']} preemptions, "
                  f"{r['requeues']} requeues, "
                  f"{r['tokens_recomputed']} tokens recomputed, "
                  f"tokens identical: {r['tokens_identical']}, "
                  f"invariants ok: {r['invariants_ok']}")
        if r["config"].startswith("overload_shed"):
            print(f"{r['config']}: queue_limit={r['queue_limit']}, "
                  f"{r['shed_queue_full']} shed (typed: "
                  f"{r['all_outcomes_typed']}), accepted identical: "
                  f"{r['accepted_identical']}")
        if r["config"].startswith("mesh_dp_scaling"):
            print(f"{r['config']}: {r['tokens_per_s_r1']:.1f} -> "
                  f"{r['tokens_per_s_rN']:.1f} tok/s "
                  f"({r['dp_scaling']:.2f}x DP scaling), "
                  f"{r['warm_cross_replica_promotions']} warm "
                  f"cross-replica promotions, tokens identical: "
                  f"{r['tokens_identical']}")

    record = {
        "benchmark": "continuous_batching",
        "config": cfg.name,
        "requests": args.requests,
        "max_new": args.max_new,
        "capacity": args.capacity,
        "results": rows,
    }
    with open(args.json_out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.json_out}")

    if args.check_chunked:
        # CI gate: the chunked route must have compiled exactly ONE
        # prefill executable per (chunk shape, quant mode) config, and
        # every paged row must carry TTFT data
        bad = []
        chunked_rows = [r for r in timed if "_chunked_b" in r["config"]]
        if not chunked_rows:
            bad.append("no chunked config rows in the artifact")
        for r in chunked_rows:
            budget = r.get("prefill_chunk_shapes", 1)
            compiles = r.get("prefill_compiles", 0)
            if compiles > budget:
                bad.append(f"{r['config']}: {compiles} prefill "
                           f"executables (expected <= {budget}, one per "
                           f"chunk shape)")
        for r in timed:
            if ("_staged_b" in r["config"] or "_chunked_b" in r["config"]) \
                    and "ttft_mean_s" not in r:
                bad.append(f"{r['config']}: missing ttft_mean_s")
        if not any(r["config"].startswith("chunked_vs_staged")
                   for r in rows):
            bad.append("missing chunked_vs_staged summary rows")
        if bad:
            raise SystemExit("--check-chunked FAILED:\n  " +
                             "\n  ".join(bad))
        print("--check-chunked OK: at most one compiled prefill per "
              "chunk shape, TTFT rows present")

    if args.check_packed:
        # CI gate for the packed admission route: token identity vs
        # chunked on the burst workload, ONE prefill dispatch per packed
        # engine step (vs one per admission chunk on the chunked route),
        # at most one compiled executable per packed bucket, and TTFT
        # p95 no worse than chunked.  Perf margins beyond that are
        # reported, not gated — a shared CI box cannot promise ratios.
        bad = []
        summary = [r for r in rows
                   if r["config"].startswith("packed_vs_chunked")]
        if not summary:
            bad.append("no packed_vs_chunked summary row in the artifact")
        for r in summary:
            if not r["tokens_identical"]:
                bad.append(f"{r['config']}: packed tokens diverge from "
                           f"chunked on the burst workload")
            if r["ttft_p95_packed_s"] > r["ttft_p95_chunked_s"]:
                bad.append(f"{r['config']}: packed ttft p95 "
                           f"{1e3 * r['ttft_p95_packed_s']:.1f}ms worse "
                           f"than chunked "
                           f"{1e3 * r['ttft_p95_chunked_s']:.1f}ms")
        for r in timed:
            if not r["config"].startswith("packed_burst_b"):
                continue
            if r["prefill_dispatches"] != r["prefill_packed_steps"]:
                bad.append(f"{r['config']}: {r['prefill_dispatches']} "
                           f"dispatches over {r['prefill_packed_steps']} "
                           f"packed steps (expected one per step)")
            if r["prefill_chunks"] <= r["prefill_dispatches"]:
                bad.append(f"{r['config']}: burst never packed more than "
                           f"one admission per dispatch "
                           f"({r['prefill_chunks']} chunks over "
                           f"{r['prefill_dispatches']} dispatches)")
            if r["prefill_compiles"] > r["packed_buckets"]:
                bad.append(f"{r['config']}: {r['prefill_compiles']} "
                           f"prefill executables (expected <= "
                           f"{r['packed_buckets']}, one per packed "
                           f"bucket)")
        if bad:
            raise SystemExit("--check-packed FAILED:\n  " + "\n  ".join(bad))
        print("--check-packed OK: packed tokens identical to chunked, "
              "one dispatch per packed step, compiles bounded by the "
              "bucket ladder, ttft p95 no worse")

    if args.check_spec:
        # CI gate: speculative rows must exist with real rounds, and
        # speculative greedy decode must be TOKEN-IDENTICAL to plain
        # greedy decode on the standard workload (fresh engines, one
        # pass, fp — and int8 when it ran).  The 1.5x perf target is
        # deliberately NOT gated here: a shared CI box cannot promise
        # wall-clock ratios, only correctness.
        bad = []
        spec_rows = [r for r in timed if "_spec_long_b" in r["config"]]
        if not spec_rows:
            bad.append("no speculative config rows in the artifact")
        for r in spec_rows:
            if r.get("spec_rounds", 0) <= 0:
                bad.append(f"{r['config']}: no speculative rounds ran")
        quants = [q for q, _ in paged_variants]
        for quant in quants:
            outs = {}
            for spec in (False, True):
                peng = PagedEngine(cfg, params,
                                   max_batch=args.batches[-1],
                                   capacity=args.capacity,
                                   max_new_tokens=args.max_new,
                                   block_size=8, enable_partial=True,
                                   kv_quant=quant, prefill_mode="chunked",
                                   speculative=spec,
                                   gamma=min(args.gamma, 8))
                peng.precache(CACHED)
                sched = ContinuousBatchingScheduler(peng)
                for p in prompts:
                    sched.submit(p, max_new_tokens=args.max_new)
                done = sched.run()
                peng.check_invariants()
                outs[spec] = {r.prompt: list(r.result.token_ids)
                              for r in done if r.result is not None}
            for p in prompts:
                if outs[False].get(p) != outs[True].get(p):
                    bad.append(f"spec tokens diverge from plain greedy "
                               f"(quant={quant}): {p!r}")
        if bad:
            raise SystemExit("--check-spec FAILED:\n  " + "\n  ".join(bad))
        print("--check-spec OK: speculative greedy token-identical to "
              "plain greedy, rounds > 0")

    if args.check_semantic:
        # CI gate for the tentpole claim: the semantic workload shows
        # reuse where the prefix paths see none, fidelity is recorded
        # (and clears --fidelity-min), and the prefix paths are
        # byte-preserved under semantic mode
        bad = []
        on_rows = [r for r in rows
                   if r["config"].startswith("semantic_on_b")]
        off_rows = [r for r in rows
                    if r["config"].startswith("semantic_off_b")]
        if not on_rows:
            bad.append("no semantic_on rows in the artifact")
        for r in off_rows:
            if r["reuse_depth_max"] != 0:
                bad.append(f"{r['config']}: prefix paths reported reuse "
                           f"{r['reuse_depth_max']} on the prefix-free "
                           f"workload")
        for r in on_rows:
            if r["semantic_grafts"] <= 0:
                bad.append(f"{r['config']}: no grafts on the semantic "
                           f"workload")
            if r["reuse_depth_mean"] <= 0:
                bad.append(f"{r['config']}: zero reuse depth despite "
                           f"semantic mode")
        for r in rows:
            if r["config"].startswith("semantic_vs_exact") \
                    and r["fidelity"] < args.fidelity_min:
                bad.append(f"{r['config']}: fidelity {r['fidelity']:.3f}"
                           f" < {args.fidelity_min}")
        pres = [r for r in rows if r["config"] == "semantic_preservation"]
        if not pres:
            bad.append("missing semantic_preservation row")
        elif not pres[0]["preserved"]:
            bad.append(f"semantic mode changed {pres[0]['mismatches']} "
                       f"prefix-path request(s)")
        if bad:
            raise SystemExit("--check-semantic FAILED:\n  " +
                             "\n  ".join(bad))
        print("--check-semantic OK: grafted reuse where prefix paths "
              "report zero, prefix paths preserved")

    if args.check_mesh:
        # CI gate for the mesh-sharded server: correctness (token
        # identity vs the single-device paged engine), warm cross-replica
        # admission (block promotion, not recompute), and DP scaling
        # above 1.0x at D replicas.  The scaling bar is deliberately
        # just ">1": a shared CI box cannot promise linear scaling.
        bad = []
        mesh_rows = [r for r in timed if r["config"].startswith("mesh_")]
        if not mesh_rows:
            bad.append("no mesh config rows in the artifact")
        for r in mesh_rows:
            if not r.get("tokens_identical"):
                bad.append(f"{r['config']}: sharded tokens diverge from "
                           f"the single-device paged engine")
        scal = [r for r in rows
                if r["config"].startswith("mesh_dp_scaling")]
        if not scal:
            bad.append("missing mesh_dp_scaling summary row")
        for r in scal:
            if r["dp_scaling"] <= 1.0:
                bad.append(f"{r['config']}: DP scaling "
                           f"{r['dp_scaling']:.2f}x <= 1.0x")
            if r["warm_cross_replica_promotions"] <= 0:
                bad.append(f"{r['config']}: warm pass promoted nothing "
                           f"across replicas (recompute instead?)")
        if bad:
            raise SystemExit("--check-mesh FAILED:\n  " + "\n  ".join(bad))
        print("--check-mesh OK: sharded tokens identical, warm "
              "cross-replica promotions > 0, DP scaling > 1.0x")

    if args.check_preempt:
        # CI gate for pressure-safe serving: the undersized pool must
        # have actually preempted (otherwise the workload proved
        # nothing), every non-shed request must match the uncapped run
        # token-for-token, the pool invariants must hold afterwards,
        # and every terminal request must carry a typed outcome.
        bad = []
        over = [r for r in rows if r["config"].startswith("overload_b")]
        if not over:
            bad.append("no overload rows in the artifact")
        for r in over:
            if r["preemptions"] < 1:
                bad.append(f"{r['config']}: pool never preempted "
                           f"(num_blocks={r['num_blocks']} too big for "
                           f"the workload?)")
            if not r["tokens_identical"]:
                bad.append(f"{r['config']}: preempted-then-resumed "
                           f"tokens diverge from the uncapped run")
            if not r["invariants_ok"]:
                bad.append(f"{r['config']}: pool invariants violated "
                           f"after the overload run")
            if r["preempt_errors"]:
                bad.append(f"{r['config']}: {r['preempt_errors']} "
                           f"requests errored instead of resuming")
        shed_rows = [r for r in rows
                     if r["config"].startswith("overload_shed")]
        if not shed_rows:
            bad.append("missing overload_shed row")
        for r in shed_rows:
            if r["shed_queue_full"] < 1:
                bad.append(f"{r['config']}: bounded queue never shed")
            if not r["all_outcomes_typed"]:
                bad.append(f"{r['config']}: some terminal request has "
                           f"no typed outcome")
            if not r["accepted_identical"]:
                bad.append(f"{r['config']}: accepted requests' tokens "
                           f"changed when neighbours were shed")
        if bad:
            raise SystemExit("--check-preempt FAILED:\n  " +
                             "\n  ".join(bad))
        print("--check-preempt OK: preemptions fired, non-shed tokens "
              "identical to the uncapped run, invariants held, sheds "
              "typed")

    return rows


if __name__ == "__main__":
    main()
