"""One run of one cell, from process start to the result line.

Set-up (counted in ``setup_s``): weights from the seed, the engine and its
pool, warm-up of every shape the mix uses, and the pre-roll of the closed
loop.  Then the window: ``seconds`` of the loop, closed at the first step
boundary after it.  After the window: the device's peak memory, the
program's state freed, and the comparison with the reference, which is
not counted anywhere.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from harness import check, flops, rows, serve, spec, trace


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _compile_cache(root: str) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however small or quick to build, comes from the
    # cache after the first run, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _pct(x, q):
    return float(np.percentile(np.asarray(x, float), q)) if len(x) else None


def _ms(x, q):
    v = _pct(x, q)
    return None if v is None else v * 1e3


def run(name: str, seed: int, seconds: float, traced: bool, *,
        t_proc: float, root: str = spec.ROOT, require_tpu: bool = True,
        bench: dict = None, traffic_dir: str = None,
        min_tokens: int = 320) -> dict:
    """``bench`` and ``traffic_dir`` stand in for the checkout's
    BENCHMARK.json and ``traffic/`` (the tests' tiny cell)."""
    bench = bench or spec.load_benchmark(root)
    cell = spec.workload(bench, name)
    cfg = spec.config(bench, cell["config"], root)
    mix = spec.traffic(cell["traffic"], traffic_dir)
    srv = cfg["serving"]

    import jax
    if require_tpu:
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
            raise NoChip(f"cell {name} needs {cell['chips']} TPU chip(s); "
                         f"JAX found {len(devs)} {devs[0].platform} "
                         f"device(s)")
    devs = jax.devices()[:cell["chips"]]
    if require_tpu:
        _compile_cache(root)
    clog = serve.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(clog)

    from repro.config import ModelConfig
    from repro.serving import PagedEngine
    from repro.serving.scheduler import ContinuousBatchingScheduler
    from harness.weights import make_params

    t = {"start": t_proc, "imports": time.perf_counter()}
    model = cfg["model"]
    params = make_params(model, seed)
    t["weights"] = time.perf_counter()
    # "engine" holds any further PagedEngine options a configuration
    # states (an int8 pool, packed admission), so a cell that needs one is
    # data alone
    engine = PagedEngine(ModelConfig(**model), params,
                         max_batch=srv["rows"], capacity=srv["capacity"],
                         block_size=srv["block_size"],
                         **srv.get("engine", {}))
    sched = ContinuousBatchingScheduler(engine)
    t["engine"] = time.perf_counter()
    cold = serve.warm_up(sched, seed,
                         serve.warmup_shapes(engine.chunk_shapes))
    t["warmup"] = time.perf_counter()

    annotate = (jax.profiler.TraceAnnotation if traced
                else (lambda _name: contextlib.nullcontext()))
    loop = serve.Loop(sched, mix, srv["rows"], srv["capacity"], seed,
                      annotate)
    loop.start(time.perf_counter())
    loop.run_until(loop.t0 + mix["preroll_s"])
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        jax.profiler.start_trace(log_dir,
                                 profiler_options=trace.profile_options())

    # ---- the window -------------------------------------------------
    w0 = time.perf_counter()
    snap0, s0 = rows.snapshot(sched), len(loop.steps)
    c0 = {**sched.stats, **{f"engine.{k}": v
                            for k, v in engine.stats.items()}}
    with annotate(trace.WINDOW_SPAN):
        loop.run_until(w0 + seconds)
    w1 = time.perf_counter()
    snap1, s1 = rows.snapshot(sched), len(loop.steps)
    c1 = {**sched.stats, **{f"engine.{k}": v
                            for k, v in engine.stats.items()}}
    tr = None
    if traced:
        jax.profiler.stop_trace()
        events = trace.extract(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        tr = trace.reduce(events)

    mem = [d.memory_stats() or {} for d in devs]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    # ---- end-to-end numbers -----------------------------------------
    reqs = list(loop.reqs.values())
    due = [r for r in reqs if w0 <= r.due < w1]
    ttft = []
    for r in due:
        first = r.first
        if first is None and r.rid in snap1:
            first = snap1[r.rid][3]
        ttft.append((min(first, w1) if first is not None else w1) - r.due)
    done = [r for r in reqs if r.done is not None and w0 < r.done <= w1]
    tpot = [(r.done - r.first) / (r.gen - 1) for r in done
            if r.first is not None and r.gen >= 2]
    tokens = (serve.tokens_emitted(loop, w1, snap1)
              - serve.tokens_emitted(loop, w0, snap0))
    window_s = w1 - w0
    failed = sum(1 for r in due if r.done is not None and not r.ok)
    metrics = {
        "ttft_p50_ms": (_pct(ttft, 50) * 1e3 if ttft else None, "ms"),
        "tpot_p90_ms": (_pct(tpot, 90) * 1e3 if tpot else None, "ms"),
        "output_tokens_per_s": (tokens / window_s, "tokens/s"),
        "setup_s": (w0 - t_proc, "s"),
    }

    # ---- per-layer context (read by metrics/<name>.py) ---------------
    adm = [(m, reused) for rid, (s, m, reused) in loop.admitted.items()
           if s0 <= s < s1]
    kind = devs[0].device_kind
    w = SimpleNamespace(
        counters0=c0, counters1=c1, trace=tr,
        decode_ctx=loop.decode_ctx[s0:s1], admissions=adm,
        dims=flops.Dims.from_model(model),
        peaks=flops.PEAKS.get(kind) if not require_tpu
        else flops.peaks(kind))

    lateness = [r.submit - r.due for r in due]
    step_s = np.diff([w0] + [e for _, e in loop.steps[s0:s1]])
    diag = {
        "setup_s": {"imports": t["imports"] - t_proc,
                    "weights": t["weights"] - t["imports"],
                    "engine": t["engine"] - t["weights"],
                    "warmup": t["warmup"] - t["engine"],
                    "preroll": w0 - t["warmup"],
                    # backend compiles and compile-cache loads alike
                    "programs": clog.count("backend_compile_duration",
                                           t_proc, w0),
                    "programs_s": clog.seconds("backend_compile_duration",
                                               t_proc, w0)},
        "window": {"seconds": window_s, "steps": s1 - s0,
                   "step_ms_p50": _ms(step_s, 50),
                   "step_ms_p90": _ms(step_s, 90),
                   "compiles": clog.count("backend_compile_duration",
                                          w0, w1),
                   "traces": clog.count("jaxpr_trace_duration", w0, w1)},
        "generator_late_ms": {"p50": _ms(lateness, 50),
                              "max": _ms(lateness, 100)},
        "requests": {"attempted": len(due), "completed": len(done),
                     "in_flight_at_end": len(snap1),
                     "plan_exhausted": loop.plan_exhausted,
                     "preemptions": c1["preemptions"] - c0["preemptions"]},
        "ttft_ms": {"p50": _ms(ttft, 50), "p90": _ms(ttft, 90),
                    "max": _ms(ttft, 100)},
        "tpot_p50_ms": _ms(tpot, 50),
        "tokens": tokens,
        "memory_peak_bytes": peak,
    }

    # ---- free the program's state, then the reference ----------------
    loop.sched = None
    del sched, engine
    gc.collect()
    ref = spec.reference(cfg)
    compared = check.sample(done, seed, min_tokens) + [
        r for r in cold if r.ok]
    t_ref = time.perf_counter()
    gap = check.gaps(ref, params, model, compared)
    diag["reference_s"] = time.perf_counter() - t_ref
    checks, correct = check.judge(
        float(gap.max()) if len(gap) else None, failed,
        int(sum(r.gen for r in compared)), cfg["checks"])

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": failed}
    if traced:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        vals = {}
        for m in spec.per_layer_for(bench, name):
            v = spec.metric_reader(m["name"]).read(w)
            if v is not None:
                vals[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = vals
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        ends = spec.end_to_end_for(bench, name)
        out["metrics"] = {k: {"value": v, "unit": u}
                          for k, (v, u) in metrics.items()
                          if v is not None and k in ends}
    out["device"] = device
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    diag["requests"]["compared"] = len(compared)
    return {"result": out, "diagnostics": diag,
            "context": w, "compared": compared, "params": params,
            "model": model, "reference": ref, "limits": cfg["checks"]}
