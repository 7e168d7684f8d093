"""The traffic generator: the same seed gives the same plan, every seed
the same stratified multiset of lengths in its own order, and each turn's
prompt extends the previous one."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import traffic  # noqa: E402

MIX = json.load(open(os.path.join(os.path.dirname(HERE), "traffic",
                                  "sessions.json")))
BIG_SEED = 2 ** 31 + 12345


def _lengths(plans, r):
    return (sorted(p.turns[r].user_tokens for p in plans),
            sorted(p.turns[r].reply_tokens for p in plans),
            sorted(round(p.turns[r].think_s, 9) for p in plans))


def _flat(plans):
    return [(p.start_s, [(t.user_tokens, t.reply_tokens, t.think_s,
                          t.new_session, t.persona) for t in p.turns])
            for p in plans]


def test_the_seed_picks_the_text_and_the_mix_the_plan():
    assert _flat(traffic.plan(MIX, 48, BIG_SEED)) == \
        _flat(traffic.plan(MIX, 48, BIG_SEED))
    assert traffic.user_text(BIG_SEED, 3, 5, 40) == \
        traffic.user_text(BIG_SEED, 3, 5, 40)
    assert traffic.user_text(BIG_SEED, 3, 5, 40) != \
        traffic.user_text(1, 3, 5, 40)
    assert traffic.personas(MIX, BIG_SEED) == \
        traffic.personas(MIX, BIG_SEED)
    assert traffic.personas(MIX, BIG_SEED) != traffic.personas(MIX, 1)


def test_arrangements_order_one_stratified_multiset():
    a = traffic.plan(MIX, 48, BIG_SEED)
    b = traffic.plan(MIX, 48, 1)
    assert _flat(a) != _flat(b)
    for r in range(MIX["rounds"]):
        assert _lengths(a, r) == _lengths(b, r)
    q = (np.arange(48) + 0.5) / 48
    ut = MIX["user_turn"]
    want = np.clip(np.rint(ut["median"] * np.exp(ut["sigma"] * np.array(
        [__import__("statistics").NormalDist().inv_cdf(x) for x in q]))),
        ut["min"], ut["max"])
    assert _lengths(a, 0)[0] == sorted(want.astype(int).tolist())
    assert sorted(p.start_s for p in a) == sorted(p.start_s for p in b)
    # session lengths: the first session of every user, stratified
    first = sorted(next(i for i, t in enumerate(p.turns[1:], 1)
                        if t.new_session) for p in a)
    assert first == sorted(traffic._quantiles(
        dict(MIX["session_turns"], dist="int_uniform"), 48).tolist())


def test_lengths_are_the_mixes_and_personas_balanced():
    plans = traffic.plan(MIX, 48, 7)
    ut, rp, th = MIX["user_turn"], MIX["reply"], MIX["think_s"]
    assert all(ut["min"] <= t.user_tokens <= ut["max"]
               and rp["min"] <= t.reply_tokens <= rp["max"]
               and th["min"] <= t.think_s <= th["max"]
               for p in plans for t in p.turns)
    n = MIX["personas"]
    firsts = [p.turns[0].persona for p in plans]
    assert sorted(firsts) == sorted(np.arange(48) % n)
    ps = traffic.personas(MIX, 7)
    assert len(ps) == n and all(len(p) == MIX["persona_tokens"] - 1
                                for p in ps)


def test_turn_prompt_extends_the_previous_one_and_fits():
    plans = traffic.plan(MIX, 4, 11)
    ps = traffic.personas(MIX, 11)
    s = traffic.Session(ps, 1024)
    prev = None
    for k, turn in enumerate(plans[0].turns[:12]):
        text = traffic.user_text(11, 0, k, turn.user_tokens)
        p = s.prompt(turn, text)
        assert len(p) + 1 + turn.reply_tokens <= 1024
        if turn.new_session:
            assert p.startswith(ps[turn.persona])
        else:
            assert p.startswith(prev)
        s.reply(np.arange(turn.reply_tokens))
        prev = p + traffic.render_reply(np.arange(turn.reply_tokens))
    assert len(traffic.render_reply([0, 94, 95, 50256])) == 4


def test_oldest_turns_are_dropped_to_fit():
    s = traffic.Session(["p" * 191], 300)
    t = traffic.Turn(40, 20, 1.0, True, 0)
    s.prompt(t, "a" * 40)
    s.reply(np.arange(20))
    t2 = traffic.Turn(40, 20, 1.0, False, 0)
    p = s.prompt(t2, "b" * 40)
    # 1 + 191 + 40 + 20 + 40 + 20 > 300: the first turn and reply go
    assert p == "p" * 191 + "b" * 40
