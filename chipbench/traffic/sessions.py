"""Multi-turn chat sessions over shared system prompts (open loop).

Sessions open at a fixed count per ``every_s`` period, placed uniformly in
it by the seed.  Each opens on one of ``system_prompts.count`` system
prompts (lengths evenly spaced over [lo, hi], tokens from the seed), picked
with Zipf(``zipf``) popularity, and runs 1 to ``turns.max`` turns
(geometric with parameter ``turns.p``, truncated).  A turn's prompt is the
conversation so far -- the system prompt, then every earlier turn's user
part and answer -- plus a new user part; ``prefix_len`` marks the
conversation so far as shareable.  The next turn is due an exponential
think time (mean ``think_s``) after the previous answer finished, so the
generator reacts to the served answers through ``on_finish``.

As in the other kinds, the seed changes order, not the amount or timing
of the work: per period the sessions open at times keyed by the period
alone, and the system-prompt picks, turn counts, (user, answer) lengths
and think times are fixed stratified multisets -- one for the sessions
opening in the window, one for the lead-in's -- that the seed permutes.
A turn that would not fit ``max_seq`` ends its session.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.traffic import _PERIOD_KEY, Spec, length_pairs, rng_for

_SYSTEM_KEY = 1 << 40


def zipf_picks(n: int, count: int, s: float) -> np.ndarray:
    """``n`` picks among ``count`` items at the stratified quantiles of a
    Zipf(s) law (item i has weight 1 / (i + 1)^s), ascending."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u, side="right"), count - 1)


def turn_counts(n: int, max_turns: int, p: float) -> np.ndarray:
    """``n`` turn counts at the stratified quantiles of a geometric(p) law
    truncated to [1, max_turns]."""
    w = p * (1.0 - p) ** np.arange(max_turns)
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return 1 + np.minimum(np.searchsorted(cdf, u, side="right"),
                          max_turns - 1)


class Sessions:
    closed = False

    def __init__(self, params: dict, seed: int, vocab: int, batch: int,
                 max_seq: int):
        self.params, self.seed, self.vocab = params, seed, vocab
        self.max_seq = max_seq
        self.period = float(params["every_s"])
        self.n_sessions = round(params["session_rps"] * self.period)
        sp = params["system_prompts"]
        lens = np.linspace(sp["lo"], sp["hi"], sp["count"]).astype(int)
        self.system = [
            rng_for(seed, _SYSTEM_KEY + i).integers(0, vocab, n).tolist()
            for i, n in enumerate(lens)]
        self.sp = sp
        t = params["turns"]
        self.max_turns, self.turns_p = int(t["max"]), float(t["p"])
        self.think_s = float(params["think_s"])
        self.lead_in_s = float(params.get("lead_in_s", 0.0))
        self.drain_limit_s = float(params["drain_limit_s"])
        self._uid = 0
        # session id -> (conversation so far, [(user tokens, answer len,
        # think seconds)] for the turns still to come, turns issued)
        self._live: dict[int, tuple[list[int], list, int]] = {}

    @property
    def mean_rps(self) -> float:
        """Mean first turns per second (later turns come on top)."""
        return self.n_sessions / self.period

    def _turn(self, sid: int, t_due: float) -> tuple[float, Spec] | None:
        history, todo, issued = self._live[sid]
        user, olen, think = todo.pop(0)
        prompt = history + user
        if len(prompt) + olen > self.max_seq:
            del self._live[sid]
            return None
        spec = Spec(uid=self._uid, prompt=prompt, max_new_tokens=olen,
                    prefix_len=len(history), session=sid, turn=issued)
        self._uid += 1
        self._live[sid] = (prompt, todo, issued + 1)
        return t_due + think, spec

    def period_times(self, k: int) -> np.ndarray:
        """Opening times of period k's sessions, keyed by k alone."""
        return np.sort(k * self.period + rng_for(0, _PERIOD_KEY + k).uniform(
            0.0, self.period, self.n_sessions))

    def group_sessions(self, times, key: int) -> list[tuple[float, int, list]]:
        """Sessions opening at ``times``: (open time, system prompt, turns
        to come), from the group's stratified multisets of picks, turn
        counts, (user, answer) lengths and think times, in the seed's
        order."""
        n = len(times)
        rng = rng_for(self.seed, key)
        picks = rng.permutation(zipf_picks(n, self.sp["count"],
                                           self.sp["zipf"]))
        turns = rng.permutation(turn_counts(n, self.max_turns, self.turns_p))
        # one (user, answer) pair and think time per turn of the group:
        # the turn counts are a fixed multiset, so their sum is too
        m = int(turns.sum())
        pairs = length_pairs(m, self.params["user"], self.params["output"])
        pairs = [pairs[i] for i in rng.permutation(m)]
        u = (np.arange(m) + 0.5) / max(m, 1)
        thinks = list(rng.permutation(-self.think_s * np.log1p(-u)))
        out = []
        for j in range(n):
            todo = []
            for _ in range(int(turns[j])):
                ulen, olen = pairs.pop()
                todo.append((rng.integers(0, self.vocab, ulen).tolist(),
                             olen, float(thinks.pop())))
            out.append((float(times[j]), int(picks[j]), todo))
        return out

    def schedule(self, until_s: float) -> list[tuple[float, Spec]]:
        """First turns of every session opening in [-lead_in_s, until_s)."""
        times = []
        k = math.floor(-self.lead_in_s / self.period)
        while k * self.period < until_s:
            times += [t for t in self.period_times(k)
                      if -self.lead_in_s <= t < until_s]
            k += 1
        out = []
        sid = 0
        for key, group in ((0, [t for t in times if t < 0]),
                           (1, [t for t in times if t >= 0])):
            for t, pick, todo in self.group_sessions(group, key):
                self._live[sid] = (list(self.system[pick]), todo, 0)
                # the first turn is due at the open time: no think time
                todo[0] = (todo[0][0], todo[0][1], 0.0)
                nxt = self._turn(sid, t)
                if nxt is not None:
                    out.append((t, nxt[1]))
                sid += 1
        return out

    def on_finish(self, spec: Spec, generated: list[int],
                  t_done: float) -> list[tuple[float, Spec]]:
        """The session's next turn, due ``think`` seconds after the answer:
        its prompt carries this turn's prompt and answer."""
        sid = spec.session
        if sid not in self._live:
            return []
        history, todo, issued = self._live[sid]
        if not todo:
            del self._live[sid]
            return []
        self._live[sid] = (history + list(generated), todo, issued)
        nxt = self._turn(sid, t_done)
        return [] if nxt is None else [nxt]
