"""The benchmark's one traffic generator.

A traffic mix is a data file, ``chipbench/traffic/<name>.json``, whose
``kind`` picks one of the shapes below and whose other keys are its
parameters.  A cell names its mix in ``BENCHMARK.json``; adding a mix of an
existing kind adds only a data file.

Kinds:

  backlog        closed loop: the generator keeps ``queued_per_slot`` x
                 batch requests queued ahead of the engine at all times.
  poisson_burst  open loop: a steady rate plus a burst of extra rate for
                 ``length_s`` seconds of every ``every_s``.
  sessions       open loop, multi-turn: see ``chipbench/traffic/sessions.py``.

Every seed gets the same work in another order.  Lengths are a fixed,
stratified multiset of the bounded-Pareto law, its quantiles at i / (n - 1)
-- so every multiset spans the whole stated range, lo and hi included --
paired prompt-to-output by a fixed permutation; the seed only permutes them
and draws the token ids.  A closed backlog repeats one multiset of
``block`` requests (about what one window serves); an open-loop mix draws
one multiset for all the window's arrivals and one for the lead-in's.
Open-loop arrival times are the same for every seed: each period
(``every_s``) holds the same count, placed uniformly by a generator keyed
by the period alone (a Poisson process conditioned on its count), and a
burst always carries the same evenly spaced share of the length multiset.
So two seeds differ by which request comes when, never by the amount or
the timing of the work.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# a period's rng is keyed by (seed, period index); the offset keeps negative
# (lead-in) periods distinct from the positive ones
_PERIOD_KEY = 1 << 20


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it; the harness turns it into the
    program's ``Request``."""

    uid: int
    prompt: list[int]
    max_new_tokens: int
    prefix_len: int = 0
    session: int | None = None      # multi-turn traffic: the session id
    turn: int = 0


def load(name: str) -> dict:
    path = os.path.join(HERE, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def rng_for(seed: int, *keys: int) -> np.random.Generator:
    """A generator keyed by the run's seed (any size of int) and ``keys``."""
    return np.random.default_rng([seed % (1 << 63), *(k % (1 << 63)
                                                     for k in keys)])


def pareto_quantile(u, lo: int, hi: int, alpha: float):
    """Inverse CDF of the Pareto(alpha) law truncated to [lo, hi], at u in
    [0, 1]; integer lengths, always inside [lo, hi] (hi at u = 1)."""
    u = np.asarray(u, np.float64)
    ratio = (lo / hi) ** alpha
    x = lo * (1.0 - u * (1.0 - ratio)) ** (-1.0 / alpha)
    return np.clip(np.floor(x + 1e-6), lo, hi).astype(np.int64)


def pareto_mean(lo: int, hi: int, alpha: float) -> float:
    """Mean of the (continuous) bounded Pareto law on [lo, hi]."""
    ratio = (lo / hi) ** alpha
    if alpha == 1.0:
        return lo * math.log(hi / lo) / (1.0 - ratio)
    return (lo ** alpha / (1.0 - ratio) * alpha / (alpha - 1.0)
            * (lo ** (1.0 - alpha) - hi ** (1.0 - alpha)))


def stratified(n: int, dist: dict) -> np.ndarray:
    """``n`` lengths at the quantiles i / (n - 1) of ``dist`` ({"lo", "hi",
    "alpha"}), ascending: the first is lo and the last hi."""
    u = np.arange(n) / (n - 1) if n > 1 else np.full(n, 0.5)
    return pareto_quantile(u, dist["lo"], dist["hi"], dist["alpha"])


def spread_pick(n: int, k: int) -> list[int]:
    """``k`` indices evenly spaced over range(n)."""
    return [int((j + 0.5) * n / k) for j in range(k)]


def length_pairs(n: int, prompt: dict, output: dict) -> list[tuple[int, int]]:
    """The fixed multiset of (prompt, output) lengths every seed shares:
    stratified prompts paired with stratified outputs by a permutation that
    does not depend on the seed."""
    p, o = stratified(n, prompt), stratified(n, output)
    perm = np.random.default_rng(n).permutation(n)
    return [(int(p[i]), int(o[perm[i]])) for i in range(n)]


class Backlog:
    """Closed loop: an endless stream of requests, block by block; each
    block is the same length multiset in an order drawn from the seed."""

    closed = True

    def __init__(self, params: dict, seed: int, vocab: int, batch: int):
        self.params, self.seed, self.vocab = params, seed, vocab
        self.queued = int(params["queued_per_slot"] * batch)
        self.lead_in_completions = int(
            params.get("lead_in_completions_per_slot", 1) * batch)
        self.pairs = length_pairs(params["block"], params["prompt"],
                                  params["output"])
        self._uid = 0
        self._buf: list[Spec] = []

    def _refill(self) -> None:
        block = self._uid // len(self.pairs)
        rng = rng_for(self.seed, block)
        for i in rng.permutation(len(self.pairs)):
            plen, olen = self.pairs[i]
            self._buf.append(Spec(
                uid=self._uid, prompt=rng.integers(0, self.vocab,
                                                   plen).tolist(),
                max_new_tokens=olen))
            self._uid += 1

    def next(self) -> Spec:
        if not self._buf:
            self._refill()
        return self._buf.pop(0)


class PoissonBurst:
    """Open loop: ``base_rps`` all the time, plus ``extra_rps`` for
    ``length_s`` seconds starting ``at_s`` into every ``every_s`` period.
    Times are seconds from the window's start; the lead-in runs the same
    schedule from ``-lead_in_s``."""

    closed = False

    def __init__(self, params: dict, seed: int, vocab: int, batch: int):
        self.params, self.seed, self.vocab = params, seed, vocab
        self.period = float(params["every_s"])
        b = params.get("burst")
        self.burst = b
        self.n_base = round(params["base_rps"] * self.period)
        self.n_burst = round(b["extra_rps"] * b["length_s"]) if b else 0
        self.lead_in_s = float(params.get("lead_in_s", 0.0))
        self.drain_limit_s = float(params["drain_limit_s"])

    @property
    def mean_rps(self) -> float:
        return (self.n_base + self.n_burst) / self.period

    def period_times(self, k: int) -> list[tuple[float, bool]]:
        """Period k's arrival times in [k * every_s, (k + 1) * every_s),
        keyed by k alone, each marked True if it belongs to the burst."""
        rng = rng_for(0, _PERIOD_KEY + k)
        t0 = k * self.period
        out = [(float(t), False) for t in
               t0 + rng.uniform(0.0, self.period, self.n_base)]
        if self.n_burst:
            b0 = t0 + self.burst["at_s"]
            out += [(float(t), True) for t in
                    b0 + rng.uniform(0.0, self.burst["length_s"],
                                     self.n_burst)]
        return sorted(out)

    def _lengths(self, arrivals: list[tuple[float, bool]], key: int):
        """One (prompt, output) pair per arrival: the group's stratified
        multiset, its burst share evenly spaced over it by total length,
        each share in the seed's order."""
        pairs = sorted(length_pairs(len(arrivals), self.params["prompt"],
                                    self.params["output"]), key=sum)
        pick = set(spread_pick(len(pairs), sum(b for _, b in arrivals)))
        rng = rng_for(self.seed, key)
        share = {flag: [p for i, p in enumerate(pairs) if (i in pick) == flag]
                 for flag in (False, True)}
        order = {flag: list(rng.permutation(len(ps)))
                 for flag, ps in share.items()}
        return [share[b][order[b].pop()] for _, b in arrivals], rng

    def schedule(self, until_s: float) -> list[tuple[float, Spec]]:
        """Every arrival due in [-lead_in_s, until_s), time-ordered, uids
        in arrival order (lead-in arrivals have negative times)."""
        times = []
        k = math.floor(-self.lead_in_s / self.period)
        while k * self.period < until_s:
            times += [a for a in self.period_times(k)
                      if -self.lead_in_s <= a[0] < until_s]
            k += 1
        out = []
        for key, group in ((0, [a for a in times if a[0] < 0]),
                           (1, [a for a in times if a[0] >= 0])):
            lens, rng = self._lengths(group, key)
            for (t, _), (plen, olen) in zip(group, lens):
                out.append((t, Spec(
                    uid=len(out), prompt=rng.integers(0, self.vocab,
                                                      plen).tolist(),
                    max_new_tokens=olen)))
        return out

    def on_finish(self, spec: Spec, generated: list[int],
                  t_done: float) -> list[tuple[float, Spec]]:
        return []


def make(params: dict, seed: int, vocab: int, batch: int, max_seq: int):
    kind = params["kind"]
    if kind == "backlog":
        return Backlog(params, seed, vocab, batch)
    if kind == "poisson_burst":
        return PoissonBurst(params, seed, vocab, batch)
    if kind == "sessions":
        from chipbench.traffic.sessions import Sessions
        return Sessions(params, seed, vocab, batch, max_seq)
    raise ValueError(f"unknown traffic kind {kind!r}")
