"""Run metrics and the paper's Table-1 summary.

Paper §4.5 metrics: latency L = t_end - t_start, reused tokens R,
output similarity cos(E_base, E_rec), plus derived average speedup
S̄ = mean((L_base - L_rec) / L_base) * 100.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, asdict
from typing import Dict, List

import numpy as np


@dataclass
class RunMetrics:
    prompt: str
    method: str                 # "baseline" | "recycled"
    latency_s: float
    prompt_tokens: int
    gen_tokens: int
    reuse_depth: int = 0
    cache_hit: bool = False
    prompt_similarity: float = 0.0
    mode: str = ""
    output_text: str = ""

    def row(self) -> Dict:
        return asdict(self)


def output_similarity(embedder, a: str, b: str) -> float:
    ea, eb = embedder.encode(a), embedder.encode(b)
    return float(np.dot(ea, eb))


def summarize_runs(baseline: List[RunMetrics], recycled: List[RunMetrics],
                   embedder=None) -> Dict:
    """Merge per-prompt rows on the text key and emit the paper's Table 1."""
    base = {r.prompt: r for r in baseline}
    rec = {r.prompt: r for r in recycled}
    keys = [k for k in base if k in rec]
    n = len(keys)
    hits = [k for k in keys if rec[k].cache_hit]
    speedups = {}
    for k in keys:
        lb, lr = base[k].latency_s, rec[k].latency_s
        speedups[k] = (lb - lr) / lb * 100.0 if lb > 0 else 0.0
    out_sims = []
    if embedder is not None:
        out_sims = [output_similarity(embedder, base[k].output_text,
                                      rec[k].output_text) for k in keys]

    def _avg(xs):
        # nan-aware: device-resident (L1) hits carry nan similarity — no
        # retrieval backs them — and must not poison the summary mean
        xs = [x for x in xs if not (isinstance(x, float) and math.isnan(x))]
        return float(np.mean(xs)) if xs else float("nan")

    return {
        "total_prompts": n,
        "cache_hits": len(hits),
        "hit_rate_pct": 100.0 * len(hits) / n if n else float("nan"),
        "total_tokens_reused": int(sum(rec[k].reuse_depth for k in keys)),
        "avg_speedup_pct": _avg(speedups.values()),
        "avg_speedup_with_cache_pct": _avg(speedups[k] for k in hits),
        "avg_speedup_no_cache_pct": _avg(
            speedups[k] for k in keys if k not in hits),
        "avg_output_similarity": _avg(out_sims),
        "avg_prompt_similarity": _avg(rec[k].prompt_similarity for k in keys),
        "high_similarity_prompts": sum(
            1 for k in keys if rec[k].prompt_similarity > 0.8),
        "latency_baseline_avg_s": _avg(base[k].latency_s for k in keys),
        "latency_recycled_avg_s": _avg(rec[k].latency_s for k in keys),
    }


class Timer:
    """Wall-clock timer with block_until_ready semantics handled by caller
    (the paper's cuda.synchronize analogue is jax block_until_ready)."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
