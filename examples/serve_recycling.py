"""End-to-end serving driver: batched requests through the FIFO scheduler
against a recycling engine, reproducing the paper's full evaluation and the
beyond-paper partial-prefix mode.

    PYTHONPATH=src python examples/serve_recycling.py [--full] [--partial]
    PYTHONPATH=src python examples/serve_recycling.py --continuous --batch 8
    PYTHONPATH=src python examples/serve_recycling.py --paged --batch 8

``--full`` uses the paper's real 345M DialoGPT config (slow on CPU).
``--continuous`` serves the recycled pass through the continuous-batching
dense slot pool instead of serial FIFO and reports the throughput ratio.
``--paged`` serves it through the paged block-table pool: requests sharing
a prefix reference the same ref-counted device blocks (copy-on-write on
divergence), warm prefixes are re-admitted with zero host→device copies,
and the host store acts as an L2 tier behind the device-resident L1 —
the run reports resident hits, host promotions and device KV bytes in use.
``--speculative`` decodes the paged pool self-speculatively (sparse-view
drafter + single-dispatch verify; greedy rows only, token-identical
output) and reports rounds, acceptance rate and tokens per round.
"""
import argparse
import json

import jax

from repro.configs import get_config
from repro.core import HashEmbedder
from repro.core.metrics import RunMetrics, summarize_runs
from repro.data.pipeline import paper_prompt_sets
from repro.models import init_params
from repro.runtime import enable_compile_cache
from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                           Engine, FIFOScheduler, PagedEngine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--partial", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="serve the recycled pass on the dense slot pool")
    ap.add_argument("--paged", action="store_true",
                    help="serve the recycled pass on the paged block-table "
                         "pool (ref-counted prefix sharing, device-resident "
                         "L1 + host L2 tiering)")
    ap.add_argument("--int8", action="store_true",
                    help="store the device KV cache in int8 (kv_quant); "
                         "on the paged pool this is the fused-dequant tier "
                         "with the fp ring tail — ~2-4x more resident "
                         "blocks per HBM byte")
    ap.add_argument("--staged-prefill", action="store_true",
                    help="serve paged admissions through the legacy "
                         "staging-cache round-trip instead of the default "
                         "paged-native chunked prefill (reference "
                         "baseline; compiles one prefill executable per "
                         "distinct suffix length)")
    ap.add_argument("--speculative", action="store_true",
                    help="decode the paged pool self-speculatively: the "
                         "same weights refine --gamma draft guesses by "
                         "fixed-point sweeps over a pre-gathered sparse "
                         "sink+recent block view and ONE batched "
                         "dispatch verifies the bundle — greedy rows "
                         "only, token-identical output, reports the "
                         "acceptance stats (implies --paged)")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--mesh", nargs=2, type=int, metavar=("D", "T"),
                    help="serve the recycled pass on D data-parallel "
                         "paged-engine replicas, each with a T-way "
                         "tensor-parallel (KV-head-sharded) block pool, "
                         "sharing one host L2 with prefix-affinity "
                         "routing (implies --paged; needs D*T devices — "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=<D*T> "
                         "before launching)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request SLO deadline in seconds: requests "
                         "whose deadline expires while still queued are "
                         "shed BEFORE claiming pool blocks (typed "
                         "outcome shed_deadline), and under pool "
                         "pressure the engine sacrifices the "
                         "latest-deadline row first (continuous/paged "
                         "schedulers only)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the scheduler admission queue: submits "
                         "beyond this depth are shed immediately with "
                         "the typed outcome shed_queue_full instead of "
                         "growing the queue without bound "
                         "(continuous/paged schedulers only)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()
    if args.speculative:
        args.paged = True
    if args.mesh:
        args.paged = True

    cfg = get_config("dialogpt-medium")
    if not args.full:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    server = None
    if args.paged:
        args.continuous = True
        paged_kw = dict(max_batch=args.batch, capacity=args.capacity,
                        max_new_tokens=args.max_new,
                        enable_partial=args.partial, block_size=16,
                        kv_quant=args.int8,
                        prefill_mode=("staged" if args.staged_prefill
                                      else "chunked"),
                        speculative=args.speculative, gamma=args.gamma)
        if args.mesh:
            from repro.launch.serve import ShardedServer
            dp, tp = args.mesh
            server = ShardedServer(cfg, params, replicas=dp, tp=tp,
                                   **paged_kw)
            # the serial baseline pass and precache run on replica 0;
            # its recycler IS the shared L2, so every replica sees the
            # precached prefixes
            engine = server.engines[0]
        else:
            engine = PagedEngine(cfg, params, **paged_kw)
    elif args.continuous:
        engine = BatchedEngine(cfg, params, max_batch=args.batch,
                               capacity=args.capacity,
                               max_new_tokens=args.max_new,
                               enable_partial=args.partial, block_size=16,
                               kv_quant=args.int8)
    else:
        engine = Engine(cfg, params, max_new_tokens=args.max_new,
                        enable_partial=args.partial, block_size=16,
                        kv_quant=args.int8)

    cache_prompts, test_prompts = paper_prompt_sets("data")
    engine.precache(cache_prompts)
    print(f"precached {len(engine.recycler.store)} prompts "
          f"({engine.recycler.store.total_bytes/1e6:.1f} MB host KV)")

    # baseline pass stays serial (it is the paper's reference numbers)
    sched = FIFOScheduler(engine, max_batch=4)
    for p in test_prompts:                   # warm compile for both shapes
        engine.warmup(p, use_recycling=False)
        engine.warmup(p)
    for p in test_prompts:
        sched.submit(p, use_recycling=False)
    # copy: run() returns the scheduler's own completed list, which the
    # clear() below would otherwise empty out from under us
    baseline_reqs = list(sched.run())
    sched.completed.clear()
    if server is not None:
        from types import SimpleNamespace
        server.run(test_prompts)             # untimed: compiles every replica
        results = server.run(test_prompts, admit=True)   # residency-routed
        recycled_reqs = [SimpleNamespace(prompt=p,
                                         result=(None if isinstance(r, str)
                                                 else r),
                                         error=(r if isinstance(r, str)
                                                else None))
                         for p, r in zip(test_prompts, results)]
        st = server.stats()
        print(f"sharded serving: {st['replicas']} replica(s), "
              f"{st['cross_replica_promotions']} cross-replica "
              f"promotion(s), {st['host_entries']} shared-L2 entries "
              f"({st['host_bytes']/1e6:.1f} MB)")
        for i, pr in enumerate(st["per_replica"]):
            print(f"  replica {i}: tp={pr['kv_tp_degree']}, "
                  f"{pr['stats']['resident_hits']} L1 hits, "
                  f"{pr['stats']['host_promotions']} L2 promotions, "
                  f"{pr['device_kv_bytes_per_device']/1e6:.2f} MB KV "
                  f"per device")
        server.check_invariants()
    elif args.continuous:
        csched = ContinuousBatchingScheduler(engine,
                                             queue_limit=args.queue_limit)
        # full untimed pass (admit=False): compiles the pool decode step AND
        # every per-suffix-length prefill the timed pass will dispatch
        for p in test_prompts:
            csched.submit(p)
        csched.run()
        csched.completed.clear()
        for k in csched.stats:               # report the timed pass only
            csched.stats[k] = 0
        # keep submission order: run() returns requests in COMPLETION order
        # (early-EOS rows finish first), which would misalign the zip below
        recycled_reqs = [csched.submit(p, admit=True,
                                       deadline_s=args.deadline_s)
                         for p in test_prompts]
        csched.run()
        print(f"continuous batching: {csched.stats['decode_steps']} decode "
              f"steps for {len(recycled_reqs)} requests, mean occupancy "
              f"{csched.mean_occupancy():.2f}/{args.batch}")
        if args.queue_limit is not None or args.deadline_s is not None:
            print(f"backpressure: queue_limit={args.queue_limit}, "
                  f"deadline_s={args.deadline_s}, "
                  f"{csched.stats['shed_queue_full']} shed (queue full), "
                  f"{csched.stats['shed_deadline']} shed (deadline), "
                  f"{csched.stats['preemptions']} preemption requeue(s)")
        if args.paged:
            print(f"paged pool: {engine.stats['resident_hits']} resident "
                  f"(L1) hits, {engine.stats['host_promotions']} host (L2) "
                  f"promotions, {engine.stats['cow_copies']} CoW copies, "
                  f"{engine.stats['h2d_bytes']/1e6:.2f} MB host->device, "
                  f"{engine.device_kv_bytes_in_use()/1e6:.2f} MB device KV "
                  f"in use")
            print(f"admission ({engine.prefill_mode}): "
                  f"{engine.stats['prefill_chunks']} chunk steps, "
                  f"{engine.stats['staging_prefills']} staged prefills, "
                  f"{engine.stats['spec_preallocs']} speculative block "
                  f"reservations, {engine.prefill_compiles()} compiled "
                  f"prefill executable(s)")
            if args.speculative:
                st = engine.stats
                acc = (st["spec_accepted_tokens"]
                       / max(st["spec_draft_tokens"], 1))
                print(f"speculative (gamma={args.gamma}): "
                      f"{st['spec_rounds']} rounds, "
                      f"{100 * acc:.0f}% drafts accepted, "
                      f"{st['spec_emitted_tokens'] / max(st['spec_rounds'], 1):.2f} "
                      f"tokens/round, {st['spec_fallback_steps']} "
                      f"fallback steps")
        print("NOTE: per-request latency below spans the whole shared batch "
              "(queue wait included); batching trades it for throughput — "
              "see perfbench/run.py for tokens/s on a TPU")
    else:
        for p in test_prompts:
            sched.submit(p, admit=True)      # recycled + admit for reuse
        recycled_reqs = list(sched.run())

    rejected = [r for r in recycled_reqs if r.result is None]
    if rejected:               # e.g. prompt > pool capacity, or shed under
        for r in rejected:     # a queue bound / expired deadline (typed)
            why = r.error or getattr(r, "outcome", None) or "rejected"
            print(f"rejected: {r.prompt[:40]!r}: {why}")
        keep = {id(r) for r in rejected}
        baseline_reqs, recycled_reqs = zip(*[
            (b, r) for b, r in zip(baseline_reqs, recycled_reqs)
            if id(r) not in keep])
    rows_b = [RunMetrics(r.prompt, "baseline", r.result.latency_s,
                         r.result.prompt_tokens, r.result.gen_tokens,
                         output_text=r.result.text) for r in baseline_reqs]
    rows_r = [RunMetrics(r.prompt, "recycled", r.result.latency_s,
                         r.result.prompt_tokens, r.result.gen_tokens,
                         r.result.reuse_depth, r.result.cache_hit,
                         r.result.prompt_similarity, r.result.mode,
                         r.result.text) for r in recycled_reqs]

    print("\nper-request:")
    for b, r in zip(rows_b, rows_r):
        sp = (b.latency_s - r.latency_s) / b.latency_s * 100
        print(f"  reuse {r.reuse_depth:3d}/{r.prompt_tokens:3d} tok  "
              f"{b.latency_s*1e3:7.1f} -> {r.latency_s*1e3:7.1f} ms "
              f"({sp:+5.1f}%)  same-output={b.output_text == r.output_text}")

    print("\npaper Table-1 summary:")
    print(json.dumps(summarize_runs(rows_b, rows_r,
                                    embedder=HashEmbedder()), indent=1))
    print("\nengine stats:", engine.stats)


if __name__ == "__main__":
    main()
