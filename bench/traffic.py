"""The one traffic generator.  A traffic mix is a JSON data file under
``bench/traffic/``; this module reads it and draws everything from the
run's ``--seed``.

Sizes and gaps are drawn by stratified sampling: each block of ``block``
consecutive draws is the same set of quantiles of the distribution, in
an order the seed permutes.  So every seed offers the same work, in
another order, and the spread between seeds is the system's, not the
mix's.

Kinds:

* ``offline``: documents queued at t=0, refilled as they finish, so a
  backlog of ``backlog_per_slot`` x ``max_slots`` waits all through the
  window.  With ``shared_prefix``, every document opens with the same
  template of ``shared_prefix.len`` tokens (drawn from the seed), and
  ``prompt_len`` counts the template too.
* ``sessions``: chat sessions opened by an open-loop gamma renewal
  process; each opens with one of ``system_prompts.count`` system
  prompts (Zipf popularity) and takes several turns, each turn's prompt
  being the whole history so far, built from the tokens actually
  generated, with an exponential think time after each reply.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import Optional

import numpy as np

from bench.weights import seed_words

KINDS = ("offline", "sessions")
_GAMMA_SAMPLE = 200_000


def load(path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    n = spec.get("shared_prefix", {}).get("len", 0)
    if n and n >= spec["prompt_len"]["min"]:
        raise ValueError(f"{path}: shared_prefix.len must be under "
                         f"prompt_len.min")
    return spec


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([*seed_words(seed), *stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a distribution spec, clipped and (for
    integer sizes) rounded."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        v = np.floor(lo + u * (hi - lo + 1))
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif kind == "exponential":
        v = -dist["mean"] * np.log1p(-u)
    elif kind == "gamma":
        # mean and coefficient of variation; quantiles read off a large
        # sample drawn with a FIXED seed (the same for every run)
        k = 1.0 / dist["cv"] ** 2
        ref = np.random.default_rng(0).gamma(k, dist["mean"] / k,
                                             _GAMMA_SAMPLE)
        v = np.quantile(ref, u)
    else:
        raise ValueError(f"unknown dist {kind!r}")
    if "min" in dist:
        v = np.clip(v, dist["min"], dist["max"])
    return v


def stratified(rng: np.random.Generator, dist: dict, n: int,
               block: int) -> np.ndarray:
    """``n`` draws: whole blocks of the same ``block`` quantiles, each
    block in its own seeded order."""
    q = quantiles(dist, block)
    out = [rng.permutation(q) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n] if out else q[:0]


def zipf_choice(rng: np.random.Generator, k: int, s: float, n: int,
                block: int) -> np.ndarray:
    """``n`` picks among ``k`` items with popularity ~ 1/rank**s, each
    block holding the same counts (largest remainder) in seeded order."""
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    counts = np.floor(p * block).astype(int)
    rest = np.argsort(-(p * block - counts))[:block - counts.sum()]
    counts[rest] += 1
    base = np.repeat(np.arange(k), counts)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n] if out else base[:0]


@dataclasses.dataclass
class Request:
    """One request as the generator offers it."""
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float
    top_p: float
    seed: int
    session: Optional[int] = None
    turn: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def _sampling(spec: dict, rng: np.random.Generator, greedy: bool):
    s = spec.get("sampling", {})
    t = 0.0 if greedy else float(s.get("temperature", 0.0))
    return t, (1.0 if greedy else float(s.get("top_p", 1.0))), \
        int(rng.integers(0, 2**31 - 1))


class Offline:
    """An endless stream of unique documents."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec, self.seed, self.vocab = spec, seed, vocab
        self.block = spec.get("block", 64)
        self._rng = rng_for(seed, 1)
        n = spec.get("shared_prefix", {}).get("len", 0)
        self.prefix = rng_for(seed, 5).integers(0, vocab, n, dtype=np.int32)
        self._plen: list[int] = []
        self._olen: list[int] = []
        self.count = 0

    def backlog(self, max_slots: int) -> int:
        return self.spec["backlog_per_slot"] * max_slots

    def next(self) -> Request:
        if self.count >= len(self._plen):
            self._plen += stratified(self._rng, self.spec["prompt_len"],
                                     self.block, self.block).astype(int).tolist()
            self._olen += stratified(self._rng, self.spec["output_len"],
                                     self.block, self.block).astype(int).tolist()
        i = self.count
        self.count += 1
        rng = rng_for(self.seed, 2, i)
        own = rng.integers(0, self.vocab, self._plen[i] - len(self.prefix),
                           dtype=np.int32)
        prompt = np.concatenate([self.prefix, own])
        greedy = float(self.spec.get("sampling", {}).get("temperature", 0.0)) == 0.0
        t, p, s = _sampling(self.spec, rng, greedy=greedy)
        return Request(prompt, self._olen[i], t, p, s)


@dataclasses.dataclass
class Session:
    index: int
    start_s: float
    system: int
    users: list            # token arrays, one per turn
    replies: list          # reply lengths, one per turn
    thinks: list           # seconds after each reply before the next turn
    greedy: bool
    seeds: list
    history: list = dataclasses.field(default_factory=list)
    turn: int = 0

    @property
    def done(self) -> bool:
        return self.turn >= len(self.users)


class Sessions:
    """Sessions opened over ``[0, window_s)`` at the mix's rate."""

    def __init__(self, spec: dict, seed: int, vocab: int, window_s: float,
                 rate: Optional[float] = None):
        self.spec, self.vocab = spec, vocab
        self.rate = float(rate if rate is not None else spec["rate_per_s"])
        block = spec.get("block", 64)
        rng = rng_for(seed, 3)
        sp = spec["system_prompts"]
        self.system = [rng.integers(0, vocab, sp["len"], dtype=np.int32)
                       for _ in range(sp["count"])]
        gap = dict(spec["arrivals"], mean=1.0 / self.rate)
        starts, t = [], 0.0
        while True:
            for g in stratified(rng, gap, block, block):
                t += float(g)
                if t >= window_s:
                    break
                starts.append(t)
            else:
                continue
            break
        n = len(starts)
        systems = zipf_choice(rng, sp["count"], sp["zipf_s"], n, block)
        turns = stratified(rng, spec["turns"], n, block).astype(int)
        n_turns = int(turns.sum())
        users = stratified(rng, spec["user_len"], n_turns, block).astype(int)
        replies = stratified(rng, spec["reply_len"], n_turns, block).astype(int)
        thinks = stratified(rng, spec["think_s"], n_turns, block)
        every = spec.get("greedy_every", 0)
        limit = spec["max_context"]
        self.sessions: list[Session] = []
        k = 0
        for i in range(n):
            srng = rng_for(seed, 4, i)
            ctx = sp["len"]
            us, rs, ts = [], [], []
            for j in range(turns[i]):
                u, r = int(users[k + j]), int(replies[k + j])
                if ctx + u + r > limit:      # stop before the context limit
                    break
                us.append(srng.integers(0, vocab, u, dtype=np.int32))
                rs.append(r)
                ts.append(float(thinks[k + j]))
                ctx += u + r
            k += int(turns[i])
            greedy = bool(every) and i % every == 0
            self.sessions.append(Session(
                i, starts[i], int(systems[i]), us, rs, ts, greedy,
                [int(srng.integers(0, 2**31 - 1)) for _ in us],
                history=list(self.system[int(systems[i])])))

    def request(self, s: Session) -> Request:
        """The session's next turn: its whole history plus the user's
        message."""
        prompt = np.concatenate([np.asarray(s.history, np.int32),
                                 s.users[s.turn]])
        smp = self.spec.get("sampling", {})
        t = 0.0 if s.greedy else float(smp.get("temperature", 0.0))
        p = 1.0 if s.greedy else float(smp.get("top_p", 1.0))
        return Request(prompt, s.replies[s.turn], t, p, s.seeds[s.turn],
                       session=s.index, turn=s.turn)

    def finished(self, s: Session, prompt: np.ndarray, reply) -> float:
        """Record a served turn; returns the think time before the next."""
        s.history = list(prompt) + [int(t) for t in reply]
        think = s.thinks[s.turn]
        s.turn += 1
        return think


def max_prompt(spec: dict) -> int:
    """Longest prompt the mix can send (sizes the warmed prefill buckets)."""
    if spec["kind"] == "offline":
        return int(quantiles(spec["prompt_len"], 1024).max())
    return int(spec["max_context"])


def max_context(spec: dict) -> int:
    """Most positions one request can fill: prompt plus output."""
    if spec["kind"] == "offline":
        return max_prompt(spec) + int(quantiles(spec["output_len"], 1024).max())
    return int(spec["max_context"])

