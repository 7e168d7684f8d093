"""A whole run of the harness on a tiny cell on the CPU (the look for a
chip skipped): a sound program comes out correct, and a program broken
under the timed path comes out not correct, once for each fault a
one-chip serving cell can have:

* a served token altered where it is produced (the greedy pick);
* a decode step that returns its state unchanged (the step's K/V never
  reach the pool, so later tokens attend a stale cache).
"""
import json
import os
import sys
import time

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import cell, check  # noqa: E402

FX = os.path.join(HERE, "fixtures")
BENCH = {"configs": [{"name": "tiny", "file": os.path.join(FX, "tiny.json")}],
         "workloads": [{"name": "tiny.sessions", "config": "tiny",
                        "traffic": "tiny_sessions", "chips": 1}],
         "end_to_end": [], "per_layer": []}
LIMIT = json.load(open(os.path.join(FX, "tiny.json")))["checks"]


def _run(seed, full=False):
    out = cell.run("tiny.sessions", seed, 1.5, False,
                   t_proc=time.perf_counter(), require_tpu=False,
                   bench=BENCH, traffic_dir=FX)
    return out if full else out["result"]


def test_sound_program_is_correct():
    res = _run(2 ** 33 + 1)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["tokens_compared"]["value"] >= \
        LIMIT["min_tokens_compared"]


def test_altered_token_is_caught(monkeypatch):
    import repro.serving.engine as engine
    greedy = engine.greedy

    def altered(logits):
        tok = greedy(logits)
        return jnp.where(tok % 5 == 0, (tok + 1) % logits.shape[-1], tok)

    monkeypatch.setattr(engine, "greedy", altered)
    res = _run(5)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        LIMIT["max_logit_gap"]


def test_decode_state_left_unchanged_is_caught(monkeypatch):
    import repro.serving.paged as paged
    step = paged.decode_step

    def frozen(cfg, params, token, pool, pos, **kw):
        logits, _ = step(cfg, params, token, pool, pos, **kw)
        return logits, pool

    monkeypatch.setattr(paged, "decode_step", frozen)
    res = _run(6)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        LIMIT["max_logit_gap"]


def test_control_in_the_programs_place_is_not_correct():
    """The reference computed in fp8, one step below the bf16 the
    configuration states, judged at the served positions of the same
    requests, reads over the limit that the program keeps."""
    out = _run(7, full=True)
    control = check.gaps(out["reference"], out["params"], out["model"],
                         out["compared"], quant="fp8")
    res = out["result"]
    checks, correct = check.judge(
        float(control.max()), res["failed"],
        res["checks"]["tokens_compared"]["value"], out["limits"])
    assert res["correct"]
    assert not correct
    assert checks["max_logit_gap"][0] > LIMIT["max_logit_gap"]


def test_no_chip_is_refused():
    with pytest.raises(cell.NoChip):
        cell.run("tiny.sessions", 1, 1.0, False, t_proc=time.perf_counter(),
                 bench=BENCH, traffic_dir=FX)
