"""End-to-end statistics on synthetic request timelines, and the traffic
generator's seeding."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import stats, traffic  # noqa: E402

W0, W1 = 10.0, 40.0


def steady(n=200, gap=0.15, ttft=0.2, tpot=0.02, tokens=30):
    out = []
    for i in range(n):
        due = W0 - 2.0 + i * gap
        first = due + ttft
        out.append(stats.Timeline(due, [first + k * tpot
                                        for k in range(tokens)]))
    return out


def stalled(reqs, at, length):
    """Every token that would land in [at, at + length) lands at the end
    of the stall instead, and everything after it shifts by the stall."""
    out = []
    for r in reqs:
        t = [x if x < at else x + length for x in r.token_times]
        out.append(stats.Timeline(r.due, t))
    return out


def test_stall_raises_ttft_and_tpot_tails():
    # 200 tokens at 20 ms: every request decodes for 4 s, so a 1.5 s
    # stall sits inside the lifetime of more than a tenth of them
    base = stats.end_to_end(steady(tokens=200), W0, W1)
    hit = stats.end_to_end(stalled(steady(tokens=200), 25.0, 1.5), W0, W1)
    assert abs(base["ttft_p90_ms"] - 200.0) < 1e-6
    assert abs(base["tpot_p90_ms"] - 20.0) < 1e-6
    assert hit["ttft_p90_ms"] > base["ttft_p90_ms"] + 300
    assert hit["tpot_p90_ms"] > base["tpot_p90_ms"] * 1.3
    assert hit["output_tokens_per_s"] < base["output_tokens_per_s"]


def test_unfinished_requests_stay_in_the_tail():
    reqs = steady()
    # the last 30 due requests never get a token: they count with their
    # wait up to the window's end, so p90 is their wait, not a drop-out
    for r in reqs[-60:]:
        if r.due >= W0:
            r.token_times.clear()
    samples = stats.ttft_samples(reqs, W0, W1)
    assert len(samples) == sum(W0 <= r.due < W1 for r in reqs)
    waits = [W1 - r.due for r in reqs[-60:] if W0 <= r.due < W1]
    assert max(samples) == max(waits)
    assert stats.end_to_end(reqs, W0, W1)["ttft_p90_ms"] > 200.0


def test_window_bounds_the_samples():
    reqs = steady()
    n = sum(W0 <= r.due < W1 for r in reqs)
    assert len(stats.ttft_samples(reqs, W0, W1)) == n
    tok = stats.tokens_in(reqs, W0, W1)
    assert tok == sum(W0 <= x <= W1 for r in reqs for x in r.token_times)


MIX = traffic.load_mix("code_fim")
CHAT = traffic.load_mix("chat_burst")


def _key(arrivals):
    return [(a.due, a.prompt.tobytes(), a.max_new, a.seed, a.greedy)
            for a in arrivals]


def test_seed_fixes_traffic_and_another_seed_changes_it():
    big = 2 ** 33 + 12345
    a = traffic.generate(MIX, big, 10, 49152)
    b = traffic.generate(MIX, big, 10, 49152)
    c = traffic.generate(MIX, big + 1, 10, 49152)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_offers_the_same_work():
    for mix in (MIX, CHAT):
        runs = [traffic.generate(mix, s, 10, 1000) for s in (1, 2, 2 ** 40)]
        sizes = [sorted((len(a.prompt), a.max_new) for a in r)
                 for r in runs]
        assert sorted(len(a.prompt) for a in runs[0]) \
            == sorted(len(a.prompt) for a in runs[1])
        assert sorted(a.max_new for a in runs[0]) \
            == sorted(a.max_new for a in runs[2])
        assert sizes[0] != sizes[1]        # paired differently
        n = traffic.count(mix, 10)
        assert all(len(r) == n for r in runs)
        # the same work in every block of arrivals, at the same pace
        b = traffic.BLOCK
        for i in range(0, n - b, b):
            blocks = [sorted(len(a.prompt) for a in r[i:i + b])
                      for r in runs]
            assert blocks[0] == blocks[1] == blocks[2]
            ends = [r[i + b].due for r in runs]
            assert max(ends) - min(ends) < 1e-9
        spans = [r[-1].due for r in runs]
        assert max(spans) <= n / mix["rate_per_s"]


def test_lengths_respect_the_clip_and_the_mix():
    for mix in (MIX, CHAT):
        arr = traffic.generate(mix, 7, 30, 1000)
        p = np.asarray([len(a.prompt) for a in arr])
        o = np.asarray([a.max_new for a in arr])
        pt, ot = mix["prompt_tokens"], mix["output_tokens"]
        assert p.min() >= pt["min"] and p.max() <= pt["max"]
        assert o.min() >= ot["min"] and o.max() <= ot["max"]
        assert 0.7 * pt["median"] < np.median(p) < 1.3 * pt["median"]
        greedy = sum(a.greedy for a in arr)
        every = mix["sampling"]["greedy_every"]
        assert greedy == -(-len(arr) // every)
