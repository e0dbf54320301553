"""The traffic generator: seeds replay, lengths stay in bounds, every seed
gets the same work in another order, and the burst mix averages 0.8 x the
knee."""

import numpy as np
import pytest

from chipbench import traffic
from chipbench.traffic import sessions as S

BACKLOG = traffic.load("backlog")
# bursty chat: 0.5 x a knee of 3 req/s, plus 1.5 x the knee for 2 s of
# every 10 s
CHAT = {"kind": "poisson_burst", "knee_rps": 3.0, "base_rps": 1.5,
        "every_s": 10.0,
        "burst": {"at_s": 6.0, "length_s": 2.0, "extra_rps": 4.5},
        "lead_in_s": 5.0, "drain_limit_s": 60.0,
        "prompt": {"lo": 64, "hi": 2048, "alpha": 1.2},
        "output": {"lo": 16, "hi": 768, "alpha": 1.2}}
SESSIONS = {
    "kind": "sessions", "session_rps": 2.0, "every_s": 10.0,
    "system_prompts": {"count": 8, "lo": 1024, "hi": 2048, "zipf": 1.0},
    "turns": {"max": 4, "p": 0.5}, "think_s": 3.0,
    "user": {"lo": 32, "hi": 512, "alpha": 1.2},
    "output": {"lo": 32, "hi": 512, "alpha": 1.2},
    "lead_in_s": 5.0, "drain_limit_s": 60.0}
BIG_SEED = 2 ** 31 + 12345


def _key(spec):
    return (tuple(spec.prompt), spec.max_new_tokens, spec.prefix_len)


def test_backlog_same_seed_same_stream_and_bounds():
    a = traffic.make(BACKLOG, BIG_SEED, 256000, 32, 4096)
    b = traffic.make(BACKLOG, BIG_SEED, 256000, 32, 4096)
    sa = [a.next() for _ in range(150)]
    sb = [b.next() for _ in range(150)]
    assert [_key(x) for x in sa] == [_key(x) for x in sb]
    assert [x.uid for x in sa] == list(range(150))
    p, o = BACKLOG["prompt"], BACKLOG["output"]
    for x in sa:
        assert p["lo"] <= len(x.prompt) <= p["hi"]
        assert o["lo"] <= x.max_new_tokens <= o["hi"]
        assert all(0 <= t < 256000 for t in x.prompt[:50])
    assert a.queued == 64 and a.lead_in_completions == 16


def test_backlog_seeds_share_each_block_multiset():
    """Two seeds: other order and other tokens, the same (prompt, output)
    lengths in every block of ``block`` requests."""
    n = BACKLOG["block"]
    a = traffic.make(BACKLOG, 1, 1000, 32, 4096)
    b = traffic.make(BACKLOG, BIG_SEED, 1000, 32, 4096)
    la = [(len(x.prompt), x.max_new_tokens) for x in
          (a.next() for _ in range(2 * n))]
    lb = [(len(x.prompt), x.max_new_tokens) for x in
          (b.next() for _ in range(2 * n))]
    assert la != lb
    for k in range(2):
        assert sorted(la[k * n:(k + 1) * n]) == sorted(lb[k * n:(k + 1) * n])
    # the multiset stands for the law: it spans the stated range, and its
    # means are the bounded Pareto's
    for i, key in enumerate(("prompt", "output")):
        got = [x[i] for x in la[:n]]
        dist = BACKLOG[key]
        assert min(got) == dist["lo"] and max(got) == dist["hi"]
        want = traffic.pareto_mean(**dist)
        assert abs(np.mean(got) - want) / want < 0.03


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_burst_schedule_replays_and_stays_in_bounds(seed):
    a = traffic.make(CHAT, seed, 32000, 8, 4096).schedule(30.0)
    b = traffic.make(CHAT, seed, 32000, 8, 4096).schedule(30.0)
    assert [(t, _key(s)) for t, s in a] == [(t, _key(s)) for t, s in b]
    times = [t for t, _ in a]
    assert times == sorted(times)
    assert -CHAT["lead_in_s"] <= times[0] and times[-1] < 30.0
    for _, s in a:
        assert CHAT["prompt"]["lo"] <= len(s.prompt) <= CHAT["prompt"]["hi"]
        assert (CHAT["output"]["lo"] <= s.max_new_tokens
                <= CHAT["output"]["hi"])


def test_burst_mean_is_four_fifths_of_the_knee():
    g = traffic.make(CHAT, 5, 32000, 8, 4096)
    knee = CHAT["knee_rps"]
    assert g.mean_rps == pytest.approx(0.8 * knee)
    assert CHAT["base_rps"] == pytest.approx(0.5 * knee)
    assert (CHAT["base_rps"] + CHAT["burst"]["extra_rps"]
            == pytest.approx(2.0 * knee))
    window = [t for t, _ in g.schedule(30.0) if t >= 0]
    assert len(window) == round(0.8 * knee * 30)
    b0 = CHAT["burst"]["at_s"]
    in_burst = [t for t in window
                if b0 <= t % CHAT["every_s"] < b0 + CHAT["burst"]["length_s"]]
    # the burst's 2 s hold its extra arrivals and their share of the base
    assert len(in_burst) >= 3 * CHAT["burst"]["extra_rps"] * 2


def test_burst_seeds_share_arrivals_and_sizes():
    """Two seeds: the same arrival times and the same lengths, in another
    order; the burst always carries the same evenly spaced share; the
    window's lengths span the stated ranges."""
    ga = traffic.make(CHAT, 1, 32000, 8, 4096)
    gb = traffic.make(CHAT, 2, 32000, 8, 4096)
    a, b = ga.schedule(50.0), gb.schedule(50.0)
    assert [t for t, _ in a] == [t for t, _ in b]
    win = [i for i, (t, _) in enumerate(a) if t >= 0]
    assert len(win) == 120
    sa = [(len(a[i][1].prompt), a[i][1].max_new_tokens) for i in win]
    sb = [(len(b[i][1].prompt), b[i][1].max_new_tokens) for i in win]
    assert sa != sb and sorted(sa) == sorted(sb)
    for key, i in (("prompt", 0), ("output", 1)):
        assert min(x[i] for x in sa) == CHAT[key]["lo"]
        assert max(x[i] for x in sa) == CHAT[key]["hi"]
    # the burst share is the same multiset for both seeds
    flags = [f for k in range(5) for _, f in ga.period_times(k)]
    in_a = sorted(x for x, f in zip(sa, flags) if f)
    in_b = sorted(x for x, f in zip(sb, flags) if f)
    assert in_a == in_b and len(in_a) == 45
    mean = lambda ps: sum(sum(p) for p in ps) / len(ps)
    rest = [x for x, f in zip(sa, flags) if not f]
    assert abs(mean(in_a) - mean(rest)) < 0.5 * mean(rest)


def test_pareto_quantiles_in_bounds():
    u = np.linspace(0.0, 1.0, 1001)
    x = traffic.pareto_quantile(u, 64, 2048, 1.2)
    assert x.min() == 64 and x.max() == 2048
    assert np.all(np.diff(x) >= 0)


def test_sessions_replay_and_prefix_is_the_conversation_so_far():
    a = traffic.make(SESSIONS, BIG_SEED, 32000, 32, 4096)
    b = traffic.make(SESSIONS, BIG_SEED, 32000, 32, 4096)
    sa, sb = a.schedule(30.0), b.schedule(30.0)
    assert [(t, _key(s)) for t, s in sa] == [(t, _key(s)) for t, s in sb]
    systems = {tuple(p) for p in a.system}
    for _, s in sa:
        # first turns: the prefix is exactly one of the system prompts
        assert tuple(s.prompt[:s.prefix_len]) in systems
        assert 1024 <= s.prefix_len <= 2048
        assert 32 <= len(s.prompt) - s.prefix_len <= 512
    # answer a first turn of a session that has more: the next turn carries
    # prompt + answer as its prefix
    multi = [s for _, s in sa if a._live.get(s.session, (0, []))[1]]
    assert multi
    first = multi[0]
    t0 = 1.0
    answer = list(range(7, 7 + first.max_new_tokens))
    nxt = a.on_finish(first, answer, t0 + 1.0)
    assert nxt
    if nxt:
        due, spec = nxt[0]
        assert due >= t0 + 1.0
        assert spec.prompt[:spec.prefix_len] == first.prompt + answer
        assert spec.prefix_len == len(first.prompt) + len(answer)
        assert spec.session == first.session and spec.turn == 1


def test_sessions_turns_and_popularity():
    counts = S.turn_counts(400, 4, 0.5)
    assert counts.min() == 1 and counts.max() == 4
    assert np.mean(counts == 1) == pytest.approx(0.5 / (1 - 0.5 ** 4),
                                                 abs=0.01)
    picks = S.zipf_picks(800, 8, 1.0)
    share0 = np.mean(picks == 0)
    assert share0 == pytest.approx(1 / sum(1 / k for k in range(1, 9)),
                                   abs=0.01)
    assert np.all(np.bincount(picks, minlength=8)[:-1]
                  >= np.bincount(picks, minlength=8)[1:])


def test_sessions_seeds_share_each_period_multiset():
    ga = traffic.make(SESSIONS, 1, 32000, 32, 4096)
    gb = traffic.make(SESSIONS, 2, 32000, 32, 4096)
    times = [t for k in range(5) for t in ga.period_times(k)]
    assert list(times) == [t for k in range(5) for t in gb.period_times(k)]
    a, b = ga.group_sessions(times, 1), gb.group_sessions(times, 1)

    def work(ss):
        return sorted((p, tuple(sorted((len(u), o) for u, o, _ in todo)))
                      for _, p, todo in ss)

    assert sorted(p for _, p, _ in a) == sorted(p for _, p, _ in b)
    assert sorted(len(t) for _, _, t in a) == sorted(len(t) for _, _, t in b)
    assert sorted(x for _, _, t in a for x in ((len(u), o) for u, o, _ in t)) \
        == sorted(x for _, _, t in b for x in ((len(u), o) for u, o, _ in t))
    assert [t for t, _, _ in a] == [t for t, _, _ in b]
    assert work(a) != work(b)
