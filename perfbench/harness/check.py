"""Whether what the timed path served is correct.

Once the window has closed, a sample drawn from the seed of the requests
the window completed, with the longest among them, plus the cold warm-up
requests, is run through the configuration's plain reference: one forward
over each prompt with its served tokens.  The number compared is the
widest gap by which a served token's logit lies below the reference's
best logit at that position (0 where the served token is the reference's
argmax).  Greedy decoding in bf16 flips only near-ties, so a sound
program reads a small gap; a wrong token reads about the spread of the
logits.

``control_gaps`` reads the same number for the control: the reference
computed one precision step below the configuration's (see the
reference module's ``quant``), put in the program's place at the same
prompts and tokens.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from harness.traffic import seed_ints


def sample(done: List, seed: int, min_tokens: int) -> List:
    """Requests to compare: the one with the most served tokens, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens are covered."""
    pool = [r for r in done if r.ok and r.gen > 0]
    if not pool:
        return []
    rng = np.random.default_rng([int(w) for w in seed_ints(seed)] + [7])
    longest = max(pool, key=lambda r: (r.gen, r.rid))
    rest = [pool[i] for i in rng.permutation(len(pool))
            if pool[i] is not longest]
    out, n = [longest], longest.gen
    for r in rest:
        if n >= min_tokens:
            break
        out.append(r)
        n += r.gen
    return out


def gaps(ref, params, model: dict, reqs: List, *, quant=None) -> np.ndarray:
    """Per request, the widest gap of its served tokens below the
    float32 reference's best logit.  With ``quant`` the tokens judged are
    not the served ones but those the lower-precision reference puts
    first at each served position (the control)."""
    out = []
    for r in reqs:
        m = len(r.served) - r.gen
        lg = ref.served_logits(params, model, r.served, m)
        toks = np.asarray(r.served[m:])
        if quant is not None:
            toks = ref.served_logits(params, model, r.served, m,
                                     quant=quant).argmax(-1)
        best = lg.max(-1)
        out.append(float((best - lg[np.arange(len(toks)), toks]).max()))
    return np.asarray(out)


def judge(gap: Optional[float], failed: int, tokens: int,
          limits: dict):
    """The numbers compared, each with its limit, and whether all keep
    their limits: the widest gap at or under ``max_logit_gap``, no
    request failed, and at least ``min_tokens_compared`` served tokens
    judged."""
    checks = {"max_logit_gap": (gap, limits["max_logit_gap"]),
              "failed_requests": (failed, 0),
              "tokens_compared": (tokens, limits["min_tokens_compared"])}
    correct = (gap is not None and gap <= limits["max_logit_gap"]
               and failed == 0 and tokens >= limits["min_tokens_compared"])
    return checks, correct
