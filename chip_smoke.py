"""Smoke run of the paged recycling server on a TPU chip.

Drives the serving path once, through the classes the launchers use, at
the full ``dialogpt-medium`` width (24 layers, d=1024, 16x64 heads, vocab
50257, bf16) with weights random-initialised from ``--seed`` and the
paper's prompt sets from ``repro.data.pipeline``:

  1. the serial paper loop (``Engine``): precache the cache prompts, then
     generate every test prompt with and without recycling;
  2. ``PagedEngine`` + ``ContinuousBatchingScheduler`` with the compiled
     Pallas kernels, fp pool then int8 pool, default chunked admission,
     batch 4: a pass that promotes prefixes from the host L2, then a warm
     pass served from device-resident blocks;
  3. the same fp request set through the jnp reference attention.

    python chip_smoke.py                    # one TPU chip
    python chip_smoke.py --four-chips       # ShardedServer 1x4 and 4x1
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced   # CPU rehearsal

``--four-chips`` runs only ``ShardedServer`` at 1x4 (TP over the 16 KV
heads) and 4x1 (four one-chip replicas), each against a single-chip
``PagedEngine`` on the same prompts.

Token rule: two paths must emit the same greedy tokens.  At the first
step where a request's tokens differ, the step is reported and both
tokens are checked against a float32 forward of the shared context: the
divergence passes only when both are within ``TIE_TOL_STD`` standard
deviations of that step's logits from the top logit, i.e. an argmax
flip on a near-tie.  In bf16 at 24 layers on random weights the logits
are nearly flat, so rounding alone can flip such a tie.

Times printed here are smoke timings of one cold run (compilation
included), not benchmark numbers.  The last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``; ``ok`` is true
only on a TPU run in which every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# A divergent greedy token passes when its float32 logit is within this
# many standard deviations (over the vocabulary) of the float32 top logit.
TIE_TOL_STD = 0.1
BLOCK = 16            # page size == radix block size
BATCH = 4             # paged engine rows
MAX_NEW = 12          # generated tokens per request
CAPACITY = 128        # positions per row: longest test prompt + MAX_NEW
ORACLE_WIDTH = 128    # padded length of the float32 teacher-forced forward


class PhaseFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailure(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (all threads)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


class Smoke:
    def __init__(self, jax, cfg, params, cache_prompts, test_prompts):
        self.jax = jax
        self.cfg, self.params = cfg, params
        self.cache_prompts, self.test_prompts = cache_prompts, test_prompts
        self.results = []          # (phase, passed)
        self.clock = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(self.clock)
        self._oracle = None

    # ------------------------------------------------------------------
    def phase(self, name, fn, *a):
        """Run one phase; print its smoke timings; record pass/fail."""
        print(f"== phase {name}", flush=True)
        t0, c0 = time.perf_counter(), self.clock.seconds
        try:
            out = fn(*a)
            passed = True
        except Exception:                  # noqa: BLE001 - reported below
            traceback.print_exc()
            out, passed = None, False
        wall = time.perf_counter() - t0
        comp = self.clock.seconds - c0
        print(f"   {name}: {'passed' if passed else 'FAILED'}; smoke timing "
              f"{wall:.1f} s wall, {comp:.1f} s of it compiling", flush=True)
        self.results.append((name, passed))
        return out

    # ------------------------------------------------------------------
    def oracle_logits(self, ids):
        """float32 logits after the token sequence ``ids`` (teacher
        forced, full-precision matmuls): the reference for a near-tie."""
        jax, jnp = self.jax, self.jax.numpy
        from repro.models.layers import unembed
        from repro.models.model import embed_inputs
        from repro.models.transformer import apply_stack
        from repro.runtime import LOCAL
        if self._oracle is None:
            cfg32 = dataclasses.replace(self.cfg, dtype="float32",
                                        param_dtype="float32")
            p32 = jax.tree.map(lambda x: x.astype(jnp.float32), self.params)

            @jax.jit
            def fwd(p, tokens, last):
                x, _ = embed_inputs(cfg32, p, tokens)
                x, _, _ = apply_stack(cfg32, p, x, mode="train", pos=0,
                                      rt=LOCAL)
                h = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
                return unembed(cfg32, p, h)[0, 0]

            self._oracle = (fwd, p32)
        fwd, p32 = self._oracle
        n = len(ids)
        check(n <= ORACLE_WIDTH, f"oracle context {n} > {ORACLE_WIDTH}")
        tokens = jnp.zeros((1, ORACLE_WIDTH), jnp.int32).at[0, :n].set(
            jnp.asarray(ids, jnp.int32))
        with jax.default_matmul_precision("highest"):
            return jax.device_get(fwd(p32, tokens, n - 1))

    def agree(self, label, prompts, ref, got):
        """Apply the token rule request by request; ``ref``/``got`` hold
        (prompt ids, generated ids) per prompt.  Raises on a failure."""
        import numpy as np
        diverged, compared = 0, 0
        for i, ((ids, a), (_, b)) in enumerate(zip(ref, got)):
            a, b = list(a), list(b)
            t = next((j for j in range(min(len(a), len(b)))
                      if a[j] != b[j]), None)
            if t is None:
                check(len(a) == len(b),
                      f"{label}: request {i} emitted {len(a)} vs {len(b)} "
                      "tokens with no differing token")
                compared += len(a)
                continue
            diverged += 1
            compared += t
            lg = np.asarray(self.oracle_logits(list(ids) + a[:t]),
                            np.float64)
            top, tol = lg.max(), TIE_TOL_STD * lg.std()
            gap = top - min(lg[a[t]], lg[b[t]])
            held = gap <= tol
            print(f"   {label}: request {i} ('{prompts[i][:32]}...') "
                  f"diverges at step {t}: token {a[t]} vs {b[t]}; float32 "
                  f"logit gap to top {gap:.5f}, tolerance {tol:.5f} "
                  f"({TIE_TOL_STD} std) -> {'near-tie' if held else 'FAIL'}",
                  flush=True)
            check(held, f"{label}: request {i} step {t} is not a near-tie")
        print(f"   {label}: {len(ref)} requests, {compared} tokens equal "
              f"before any divergence, {diverged} divergent requests, all "
              f"within the token rule", flush=True)

    # ------------------------------------------------------------------
    def serial_loop(self):
        from repro.serving import Engine
        eng = Engine(self.cfg, self.params, max_new_tokens=MAX_NEW,
                     block_size=BLOCK)
        eng.precache(self.cache_prompts)
        base = [eng.generate(p, use_recycling=False)
                for p in self.test_prompts]
        rec = [eng.generate(p) for p in self.test_prompts]
        depths = [r.reuse_depth for r in rec]
        toks = sum(r.gen_tokens for r in base + rec)
        print(f"   serial: {len(self.cache_prompts)} precached, "
              f"{2 * len(self.test_prompts)} requests, {toks} tokens, "
              f"reuse depths {depths}", flush=True)
        check(all(d > 0 for d in depths),
              f"a test prompt extending a cache prompt reused nothing: "
              f"{depths}")
        base = [split(r) for r in base]
        self.agree("serial recycled vs baseline", self.test_prompts, base,
                   [split(r) for r in rec])
        return base

    def paged(self, label, kernels, kv_quant=False):
        """Precache, then a host-promotion pass and a device-resident
        pass through the continuous-batching scheduler."""
        from repro.runtime import Runtime
        from repro.serving import PagedEngine
        eng = PagedEngine(self.cfg, self.params, max_batch=BATCH,
                          capacity=CAPACITY, block_size=BLOCK,
                          max_new_tokens=MAX_NEW, kv_quant=kv_quant,
                          rt=Runtime(use_pallas=kernels))
        check(eng.prefill_mode == "chunked", eng.prefill_mode)
        eng.precache(self.cache_prompts)
        passes = [serve(eng, self.test_prompts)
                  for _ in ("host", "resident")]
        st = eng.stats
        for name, res in zip(("host-L2 pass", "resident pass"), passes):
            depths = [r.reuse_depth for r in res]
            print(f"   {label} {name}: {len(res)} requests OK, "
                  f"{sum(r.gen_tokens for r in res)} tokens, reuse depths "
                  f"{depths}", flush=True)
            check(all(d > 0 for d in depths),
                  f"{label} {name}: a warm request reused nothing")
        print(f"   {label}: resident hits {st['resident_hits']}, host "
              f"promotions {st['host_promotions']}, prefill chunks "
              f"{st['prefill_chunks']}, decode steps "
              f"{st['batched_decode_steps']}, prefill executables "
              f"{eng.prefill_compiles()}", flush=True)
        check(st["host_promotions"] > 0 and st["resident_hits"] > 0,
              f"{label}: expected both host promotions and resident hits")
        eng.check_invariants()
        return [[split(r) for r in res] for res in passes]

    def paged_vs_serial(self, label, serial, **kw):
        passes = self.paged(label, **kw)
        for name, got in zip(("host-L2", "resident"), passes):
            self.agree(f"{label} {name} vs serial", self.test_prompts,
                       serial, got)
        return passes

    def sharded(self, replicas, tp, ref):
        from repro.launch.serve import ShardedServer
        from repro.serving.engine import GenResult
        srv = ShardedServer(self.cfg, self.params, replicas=replicas, tp=tp,
                            use_pallas=True, max_batch=BATCH,
                            capacity=CAPACITY, block_size=BLOCK,
                            max_new_tokens=MAX_NEW)
        for eng in srv.engines:        # weights placed once, per replica
            devs = set(eng.rt.mesh.devices.flat)
            check(all(leaf.devices() == devs
                      for leaf in self.jax.tree.leaves(eng.params)),
                  f"mesh {replicas}x{tp}: weights off the replica's devices")
        srv.engines[0].precache(self.cache_prompts)
        res = srv.run(self.test_prompts, max_new_tokens=MAX_NEW)
        fails = srv.shared_stats["replica_failures"]
        print(f"   mesh {replicas}x{tp}: replica_failures {fails}, "
              f"rerouted {srv.shared_stats['rerouted_requests']}, "
              f"kv_tp_degree {srv.engines[0].kv_tp_degree()}, reuse depths "
              f"{[getattr(r, 'reuse_depth', None) for r in res]}",
              flush=True)
        bad = [r for r in res if not isinstance(r, GenResult)]
        check(fails == 0, f"mesh {replicas}x{tp}: {fails} replica failures: "
                          f"{bad}")
        check(not bad, f"mesh {replicas}x{tp}: non-OK requests: {bad}")
        srv.check_invariants()
        self.agree(f"mesh {replicas}x{tp} vs one chip", self.test_prompts,
                   ref, [split(r) for r in res])

    def one_chip_ref(self):
        from repro.runtime import Runtime
        from repro.serving import PagedEngine
        eng = PagedEngine(self.cfg, self.params, max_batch=BATCH,
                          capacity=CAPACITY, block_size=BLOCK,
                          max_new_tokens=MAX_NEW,
                          rt=Runtime(use_pallas=True))
        eng.precache(self.cache_prompts)
        return [split(r) for r in serve(eng, self.test_prompts)]


def split(r):
    """(prompt ids, generated ids) of a GenResult."""
    return r.token_ids[:r.prompt_tokens], r.token_ids[r.prompt_tokens:]


def serve(eng, prompts):
    """One scheduler pass; every request must end OK."""
    from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                         RequestOutcome)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    sched.run()
    bad = [(r.request_id, r.outcome, r.error) for r in reqs
           if r.outcome != RequestOutcome.OK]
    check(not bad, f"requests not OK: {bad}")
    return [r.result for r in reqs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only ShardedServer 1x4 and 4x1 against a "
                         "single-chip PagedEngine")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at the reduced config; never "
                         "reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if args.reduced and device["platform"] == "tpu":
        print("--reduced is the CPU rehearsal; run without it on a TPU",
              file=sys.stderr)
        return 2
    if not args.reduced and device["platform"] != "tpu":
        print("no TPU found; this smoke run needs one (or --reduced on "
              "the CPU)", file=sys.stderr)
        return 1
    if args.four_chips and device["count"] < 4:
        print(f"--four-chips needs 4 devices, have {device['count']}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.data.pipeline import CACHE_PROMPTS, TEST_PROMPTS
    from repro.models import init_params
    from repro.runtime import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config("dialogpt-medium")
    if args.reduced:
        cfg = cfg.reduced()
    print(f"config: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads}x{cfg.head_dim} vocab={cfg.vocab_size} "
          f"dtype={cfg.dtype} seed={args.seed}", flush=True)
    s = Smoke(jax, cfg, init_params(cfg, jax.random.PRNGKey(args.seed)),
              list(CACHE_PROMPTS), list(TEST_PROMPTS))

    if args.four_chips:
        ref = s.phase("paged one chip (reference)", s.one_chip_ref)
        for replicas, tp in ((1, 4), (4, 1)):
            s.phase(f"sharded {replicas}x{tp}", lambda r=replicas, t=tp:
                    s.sharded(r, t, need(ref)))
    else:
        serial = s.phase("serial paper loop", s.serial_loop)
        fp = s.phase("paged fp kernels", lambda: s.paged_vs_serial(
            "paged fp", need(serial), kernels=True))
        s.phase("paged int8 kernels", lambda: s.paged_vs_serial(
            "paged int8", need(serial), kernels=True, kv_quant=True))

        def reference():
            ref = s.paged("paged fp jnp", kernels=False)
            for name, a, b in zip(("host-L2", "resident"), ref, need(fp)):
                s.agree(f"paged fp kernels vs jnp reference ({name})",
                        s.test_prompts, a, b)
        s.phase("paged fp jnp reference", reference)

    passed = all(ok for _, ok in s.results)
    print(f"phases: {', '.join(f'{n}={ok}' for n, ok in s.results)}; "
          f"compile {s.clock.seconds:.1f} s in all (smoke timing)",
          flush=True)
    if args.reduced:
        print(f"rehearsal {'passed' if passed else 'FAILED'} on "
              f"{device['platform']}; ok is reserved for a TPU run",
              flush=True)
    print(json.dumps({"ok": passed and not args.reduced, "device": device}),
          flush=True)
    return 0 if passed else 1


def need(value):
    check(value is not None, "an earlier phase this one compares with "
                             "failed")
    return value


if __name__ == "__main__":
    sys.exit(main())
