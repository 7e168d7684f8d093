"""The one traffic generator: closed-loop chat sessions, read from a mix's
parameter file (``perfbench/traffic/<mix>.json``).

Each user sends one turn, waits for the reply, thinks, and sends the next
turn; after the last turn of a session it starts a new one.  A turn's
prompt is the session's persona, then every earlier user turn and reply,
then the new user turn; the oldest turns are dropped when the prompt and
its reply would pass the context.

Every run of a cell gets the same multiset of lengths, think times and
start offsets; ``seed`` picks their order.  The plan is laid out in
rounds: round r holds the r-th request of every user, and within a round
each drawn quantity is the distribution's quantiles at (i + 1/2)/N over
the N users, dealt to the users in an order drawn from the seed.  The
seed also picks every text (personas, user turns) and, through the
weights, every reply.

The mix file's keys:

  users             "rows" (one user per pool row) or a number
  persona_tokens    tokens of each shared persona prefix, BOS included
                    (0: no shared prefix)
  personas          how many distinct personas
  user_turn, reply  {"dist": "lognormal", "median", "sigma", "min", "max"}
                    in tokens (the tokenizer is byte-level: one token per
                    byte of text)
  think_s           {"dist": "uniform", "min", "max"} seconds
  session_turns     {"min", "max"} turns per session, stratified
  stagger_s         users' first turns spread evenly over this many seconds
  preroll_s         seconds served before the measured window opens
  rounds            requests planned per user
  sources           where each value comes from (read by people only)
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import List

import numpy as np

# text alphabet: lower-case words and spaces, one byte (= one token) each
_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     ", np.uint8)
# served tokens go into the history as one printable byte each, so a reply
# of n tokens adds exactly n tokens to the next prompt whatever the weights
_PRINTABLE = 95


def seed_ints(seed: int, n: int = 4) -> np.ndarray:
    """``n`` 32-bit words from any non-negative whole number (seeds may
    exceed 32 signed bits)."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2)/n of ``spec``'s distribution."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)
    if dist == "uniform":
        return spec["min"] + (spec["max"] - spec["min"]) * q
    if dist == "int_uniform":
        lo, hi = spec["min"], spec["max"]
        return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi).astype(int)
    raise ValueError(f"unknown distribution {dist!r}")


@dataclass
class Turn:
    user_tokens: int
    reply_tokens: int
    think_s: float
    new_session: bool
    persona: int


@dataclass
class UserPlan:
    start_s: float
    turns: List[Turn] = field(default_factory=list)


def n_users(mix: dict, rows: int) -> int:
    return rows if mix["users"] == "rows" else int(mix["users"])


def plan(mix: dict, rows: int, seed: int) -> List[UserPlan]:
    """Every user's planned requests, in order."""
    users = n_users(mix, rows)
    # a stream apart from the texts', which add (user, turn) to the seed
    rng = np.random.default_rng([int(w) for w in seed_ints(seed)]
                                + [0xFFFFFFFF])
    rounds = int(mix["rounds"])
    starts = rng.permutation(users) * (mix["stagger_s"] / users)
    plans = [UserPlan(float(s)) for s in starts]
    # per round, stratified across users in the seed's order
    ut = [rng.permutation(_quantiles(mix["user_turn"], users))
          for _ in range(rounds)]
    rp = [rng.permutation(_quantiles(mix["reply"], users))
          for _ in range(rounds)]
    th = [rng.permutation(_quantiles(mix["think_s"], users))
          for _ in range(rounds)]
    turns_spec = dict(mix["session_turns"], dist="int_uniform")
    n_p = max(int(mix.get("personas", 1)), 1)
    # session lengths and personas: the s-th session of every user forms
    # one stratified set; a user never runs short (rounds bounds it)
    sess = [rng.permutation(_quantiles(turns_spec, users))
            for _ in range(rounds)]
    pers = [rng.permutation(np.arange(users) % n_p) for _ in range(rounds)]
    for u, p in enumerate(plans):
        s, left = 0, 0
        for r in range(rounds):
            new = left == 0
            if new:
                left = int(sess[s][u])
                persona = int(pers[s][u])
                s += 1
            left -= 1
            p.turns.append(Turn(int(ut[r][u]), int(rp[r][u]),
                                float(th[r][u]), new, persona))
    return plans


def make_text(rng: np.random.Generator, n: int) -> str:
    return _ALPHABET[rng.integers(0, len(_ALPHABET), n)].tobytes().decode()


def user_text(seed: int, user: int, index: int, n: int) -> str:
    """The text of ``user``'s ``index``-th turn: fixed by the seed alone,
    whatever order the closed loop sends turns in."""
    words = [int(w) for w in seed_ints(seed)] + [user, index]
    return make_text(np.random.default_rng(words), n)


def render_reply(token_ids) -> str:
    """Served tokens as history text: one printable byte per token."""
    return "".join(chr(32 + int(t) % _PRINTABLE) for t in token_ids)


class Session:
    """One user's conversation state: builds each turn's prompt text."""

    def __init__(self, personas: List[str], context: int):
        self.personas = personas
        self.context = context
        self.persona = ""
        self.history: List[str] = []       # alternating user turn, reply

    def prompt(self, turn: Turn, user_text: str) -> str:
        """The prompt of ``turn``; drops the oldest turns so that its
        tokens (BOS included) plus the reply fit the context."""
        if turn.new_session:
            self.persona = (self.personas[turn.persona]
                            if self.personas else "")
            self.history = []
        budget = self.context - turn.reply_tokens - 1 - len(self.persona)
        while (self.history
               and sum(map(len, self.history)) + len(user_text) > budget):
            del self.history[:2]
        self.history.append(user_text)
        return self.persona + "".join(self.history)

    def reply(self, token_ids) -> None:
        self.history.append(render_reply(token_ids))


def personas(mix: dict, seed: int) -> List[str]:
    """The mix's shared persona texts (BOS is the prompt's first token)."""
    n = int(mix.get("personas", 0))
    tokens = int(mix.get("persona_tokens", 0))
    if not n or not tokens:
        return []
    # persona texts take user numbers past any user's
    return [user_text(seed, (1 << 30) + i, 0, tokens - 1) for i in range(n)]
