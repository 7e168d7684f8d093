"""Serving launcher: build a recycling engine and run the paper's two-phase
evaluation (cache construction + baseline vs recycled), or an interactive
request loop.

  PYTHONPATH=src python -m repro.launch.serve --arch dialogpt-medium \
      --reduced --max-new 16 [--partial] [--compare]

``ShardedServer`` (PR 8) is the mesh-sharded front end: N data-parallel
``PagedEngine`` replicas, each TP-sharding its block pool over a
(data=1, model=T) sub-mesh, all sharing ONE host L2 (``HostKVStore``
behind one ``Recycler``).  A prefix admitted on replica 0 is a
block-granular host promotion — not a recompute — on replica 1.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve --reduced --mesh 2 2
"""
from __future__ import annotations

import argparse
import json
import os
import threading
from typing import List, Optional, Sequence, Union

import jax

from repro.configs import get_config
from repro.core import HashEmbedder
from repro.core.metrics import RunMetrics, summarize_runs
from repro.data.pipeline import paper_prompt_sets
from repro.models import init_params
from repro.runtime import enable_compile_cache
from repro.serving import Engine, PagedEngine
from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                     RequestOutcome)


class _SharedRecycler:
    """One replica's view of the SHARED Recycler (the L2 tier).

    All replicas funnel admit/lookup through one Recycler instance; this
    proxy serializes each whole operation (the recycler mutates its store
    plus three retrieval mirrors, which must stay consistent under the
    replica threads) and tags every admitted entry with the replica that
    produced it, so a hit on an entry admitted ELSEWHERE is counted as a
    cross-replica promotion candidate."""

    def __init__(self, inner, replica: int, lock, admitted_by: dict,
                 shared_stats: dict):
        self._inner = inner
        self._replica = replica
        self._lock = lock
        self._admitted_by = admitted_by
        self._shared_stats = shared_stats

    def admit(self, *args, **kw):
        with self._lock:
            entry = self._inner.admit(*args, **kw)
            if entry is not None:     # None = store refused (IO fault)
                self._admitted_by[entry.entry_id] = self._replica
            return entry

    def lookup(self, *args, **kw):
        with self._lock:
            res = self._inner.lookup(*args, **kw)
            if res.hit and res.entry is not None:
                src = self._admitted_by.get(res.entry.entry_id,
                                            self._replica)
                if src != self._replica:
                    self._shared_stats["cross_replica_promotions"] += 1
            return res

    def lookup_semantic(self, *args, **kw):
        with self._lock:
            return self._inner.lookup_semantic(*args, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class ShardedServer:
    """N data-parallel ``PagedEngine`` replicas over a shared host L2.

    Replica r owns its own devices ((data=1, model=tp) sub-mesh — see
    ``launch.mesh.serving_meshes``), its own block pool (TP-sharded over
    the KV-head axis), allocator, trie, and table mirrors.  What is
    SHARED is the host tier: one ``Recycler``/``HostKVStore``, so any
    replica serves any admitted prefix as a block-granular promotion.

    ``residency`` is the cross-replica read view of block residency: it
    peeks every replica's L1 trie (no recency stamping) and the router
    prefers the replica already holding the deepest resident prefix.
    The tries themselves stay replica-local — the view is advisory for
    routing, NOT a coherent directory (a block can be evicted between
    the peek and the admission; the admission then falls back to the
    shared L2 or a recompute, token output unchanged either way).

    ``run`` drives each replica's ``ContinuousBatchingScheduler`` in its
    own thread: engine dispatches release the GIL while the devices
    compute, so replicas genuinely overlap."""

    def __init__(self, cfg, params, *, replicas: int = 1, tp: int = 1,
                 meshes=None, use_pallas: Optional[bool] = None,
                 **engine_kw):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import serving_meshes
        from repro.runtime import on_tpu
        from repro.sharding import serving_runtime

        if meshes is None:
            meshes = serving_meshes(replicas, tp)
        if use_pallas is None:
            use_pallas = on_tpu()
        self.lock = threading.RLock()
        self._admitted_by: dict = {}
        self.shared_stats = {"cross_replica_promotions": 0,
                             "replica_failures": 0,
                             "rerouted_requests": 0}
        self.engines: List[PagedEngine] = []
        shared = None
        for r, mesh in enumerate(meshes):
            rt = serving_runtime(mesh, use_pallas=use_pallas)
            kw = dict(engine_kw)
            if shared is not None:
                kw["recycler"] = shared
            # each replica's weights live on its own devices, placed once:
            # otherwise every dispatch would copy them from the device
            # init_params left them on
            local = jax.device_put(params,
                                   NamedSharding(mesh, PartitionSpec()))
            eng = PagedEngine(cfg, local, rt=rt, **kw)
            if shared is None:
                shared = eng.recycler          # replica 0's becomes the L2
            eng.recycler = _SharedRecycler(shared, r, self.lock,
                                           self._admitted_by,
                                           self.shared_stats)
            self.engines.append(eng)
        self.recycler = shared

    # ------------------------------------------------------------------
    def residency(self, token_ids) -> List[int]:
        """Per-replica resident prefix depth for ``token_ids`` (trie peek,
        no recency stamp) — the cross-replica read view."""
        return [eng.trie.peek(token_ids)[0] for eng in self.engines]

    def _route(self, prompt: str, load: List[int]) -> int:
        ids = self.engines[0].tok.encode(prompt)
        depths = self.residency(ids)
        best = max(range(len(self.engines)),
                   key=lambda r: (depths[r], -load[r]))
        return best

    # ------------------------------------------------------------------
    def run(self, prompts: Sequence[str], *,
            replica: Union[None, int, Sequence[int]] = None,
            concurrent: Optional[bool] = None, **req_kw):
        """Route + serve ``prompts``; returns GenResults in input order.

        ``replica`` pins requests to a replica (int: all; sequence:
        per-prompt); None routes by residency then load.  ``concurrent``
        None = auto: replica threads only when the host has more than
        one core (engine dispatches release the GIL during device
        compute, but on a single core interleaved threads can only add
        contention, so the replicas run back-to-back instead)."""
        scheds = [ContinuousBatchingScheduler(eng) for eng in self.engines]
        load = [0] * len(self.engines)
        placed = []
        for i, p in enumerate(prompts):
            if replica is None:
                r = self._route(p, load)
            elif isinstance(replica, int):
                r = replica
            else:
                r = replica[i]
            placed.append((r, scheds[r].submit(p, **req_kw)))
            load[r] += 1
        if concurrent is None:
            concurrent = (os.cpu_count() or 1) > 1
        failed: dict = {}          # replica -> error message
        if concurrent and len(self.engines) > 1:
            threads = [threading.Thread(
                target=self._run_contained, args=(r, s, failed),
                daemon=True) for r, s in enumerate(scheds)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:
            for r, s in enumerate(scheds):
                self._run_contained(r, s, failed)
        if failed:
            self._reroute_failed(scheds, failed)
        return [req.result if req.result is not None else req.error
                for _, req in placed]

    def _run_contained(self, r: int, sched, failed: dict) -> None:
        """Drive one replica's scheduler; a replica failure is CONTAINED:
        only ITS in-flight requests terminate (typed ERRORED — their pool
        rows died with the replica), its untouched queue survives for
        rerouting, and the other replicas never notice."""
        try:
            sched.run()
        except Exception as e:              # noqa: BLE001 — containment
            failed[r] = f"replica {r} failed: {e}"
            with self.lock:
                self.shared_stats["replica_failures"] += 1
            for slot, req in list(sched.in_flight.items()):
                req.outcome = RequestOutcome.ERRORED
                req.error = failed[r]
                sched.completed.append(req)
            sched.in_flight.clear()

    def _reroute_failed(self, scheds, failed: dict) -> None:
        """Resubmit failed replicas' QUEUED (never-admitted) requests to
        the first healthy replica — serially, after the fleet drained, so
        the reroute cannot race a second failure (and a failure DURING
        the reroute re-enters the same containment).  Anything the shared
        L2 learned before the failure still serves these requests warm."""
        pending = list(failed)
        while pending:
            r = pending.pop(0)
            leftovers = list(scheds[r]._queue)
            scheds[r]._queue.clear()
            if not leftovers:
                continue
            healthy = [x for x in range(len(scheds)) if x not in failed]
            if not healthy:
                for req in leftovers:
                    req.outcome = RequestOutcome.ERRORED
                    req.error = "no healthy replica to reroute to"
                    scheds[r].completed.append(req)
                continue
            dst = healthy[0]
            for req in leftovers:
                scheds[dst]._queue.append(req)
                with self.lock:
                    self.shared_stats["rerouted_requests"] += 1
            before = set(failed)
            self._run_contained(dst, scheds[dst], failed)
            pending.extend(x for x in failed if x not in before)

    def check_invariants(self) -> None:
        for eng in self.engines:
            eng.check_invariants()

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        per = []
        for eng in self.engines:
            per.append({
                "stats": dict(eng.stats),
                "device_kv_bytes_in_use": eng.device_kv_bytes_in_use(),
                "device_kv_bytes_per_device":
                    eng.device_kv_bytes_per_device(),
                "kv_tp_degree": eng.kv_tp_degree(),
            })
        agg = {
            "replicas": len(self.engines),
            "cross_replica_promotions":
                self.shared_stats["cross_replica_promotions"],
            "host_entries": len(self.recycler.store),
            "host_bytes": self.recycler.store.total_bytes,
            "per_replica": per,
        }
        return agg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dialogpt-medium")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--partial", action="store_true",
                    help="enable beyond-paper block-radix partial reuse")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--compare", action="store_true",
                    help="run the paper's baseline-vs-recycled table")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, nargs=2, metavar=("D", "T"),
                    default=None,
                    help="serve through ShardedServer: D data-parallel "
                         "PagedEngine replicas x T-way TP block pools "
                         "(needs D*T devices; force host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    if args.mesh is not None:
        dp, tp = args.mesh
        server = ShardedServer(cfg, params, replicas=dp, tp=tp,
                               max_new_tokens=args.max_new,
                               enable_partial=args.partial,
                               block_size=args.block_size)
        cache_prompts, test_prompts = paper_prompt_sets("data")
        print(f"mesh {dp}x{tp}: admitting {len(cache_prompts)} prompts "
              f"on replica 0 ...")
        server.run(cache_prompts, replica=0, admit=True)
        results = server.run(test_prompts)
        for p, r in zip(test_prompts, results):
            print(f"[{r.mode:13s}] reuse={r.reuse_depth:3d}/{r.prompt_tokens}"
                  f"  '{p[:40]}...'")
        print(json.dumps(server.stats(), indent=1, default=str))
        return

    eng = Engine(cfg, params, max_new_tokens=args.max_new,
                 enable_partial=args.partial, block_size=args.block_size)

    cache_prompts, test_prompts = paper_prompt_sets("data")
    print(f"building KV cache for {len(cache_prompts)} prompts ...")
    eng.precache(cache_prompts)
    print(f"store: {len(eng.recycler.store)} entries, "
          f"{eng.recycler.store.total_bytes / 1e6:.1f} MB host bytes")

    if not args.compare:
        for p in test_prompts:
            r = eng.generate(p)
            print(f"[{r.mode:13s}] reuse={r.reuse_depth:3d}/{r.prompt_tokens}"
                  f" {r.latency_s*1e3:7.1f} ms  sim={r.prompt_similarity:.2f}"
                  f"  '{p[:40]}...'")
        return

    # paper §4.4 two-phase comparison with warmup (jit compile excluded)
    for p in test_prompts:
        eng.warmup(p, use_recycling=False)
        eng.warmup(p)
    base, rec = [], []
    for p in test_prompts:
        b = eng.generate(p, use_recycling=False)
        r = eng.generate(p)
        base.append(RunMetrics(p, "baseline", b.latency_s, b.prompt_tokens,
                               b.gen_tokens, output_text=b.text))
        rec.append(RunMetrics(p, "recycled", r.latency_s, r.prompt_tokens,
                              r.gen_tokens, r.reuse_depth, r.cache_hit,
                              r.prompt_similarity, r.mode, r.text))
        sp = (b.latency_s - r.latency_s) / b.latency_s * 100
        print(f"reuse={r.reuse_depth:3d}/{r.prompt_tokens:3d} "
              f"base={b.latency_s*1e3:7.1f}ms rec={r.latency_s*1e3:7.1f}ms "
              f"speedup={sp:5.1f}%  same_output={b.text == r.text}")
    table = summarize_runs(base, rec, embedder=HashEmbedder())
    print(json.dumps(table, indent=1))


if __name__ == "__main__":
    main()
