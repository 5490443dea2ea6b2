"""The traffic generator: deterministic in the seed, the same work for
every seed, and the stated distributions."""
import json
import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import traffic as TR  # noqa: E402


def mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


CHAT = dict(mix("chat-poisson"), rate_per_s=2.0)
BACKLOG = mix("prefix-backlog")
BIG = 2 ** 31 + 123456789          # seeds go past 32 bits


@pytest.mark.parametrize("m", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_same_seed_same_requests(m):
    a = TR.make(m, BIG, 50, 151552)
    b = TR.make(m, BIG, 50, 151552)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.arrival_s, x.deadline_s) == \
            (y.max_new, y.arrival_s, y.deadline_s)


def schedule(specs):
    return [(len(s.prompt), s.max_new, s.arrival_s, s.prefix_id)
            for s in specs]


@pytest.mark.parametrize("m", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_seeds_differ_in_order_not_in_work(m):
    m = {k: v for k, v in m.items() if k != "schedule_seed"}
    a = TR.make(m, BIG, 50, 151552)
    b = TR.make(m, BIG + 2 ** 32, 50, 151552)     # high bits count
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt) for s in b)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in b)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("m", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_a_schedule_seed_fixes_the_schedule_not_the_tokens(m):
    assert "schedule_seed" in m
    a = TR.make(m, BIG, 50, 151552)
    b = TR.make(m, BIG + 2 ** 32, 50, 151552)
    assert schedule(a) == schedule(b)
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    other = TR.make(dict(m, schedule_seed=m["schedule_seed"] + 1), BIG, 50,
                    151552)
    assert schedule(other) != schedule(a)


def test_chat_lengths_follow_the_lognormal():
    specs = TR.make(dict(CHAT, rate_per_s=20.0), 7, 50, 151552)
    p = np.array([len(s.prompt) for s in specs])
    o = np.array([s.max_new for s in specs])
    assert p.min() >= 16 and p.max() <= 1024
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 128) <= 2
    assert abs(np.median(o) - 192) <= 2
    # sigma of log length, read between the clipped tails' quartiles
    q1, q3 = np.percentile(np.log(p), [25, 75])
    assert abs((q3 - q1) / (2 * 0.6745) - 1.0) < 0.05


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    specs = TR.make(CHAT, 11, 50, 151552)
    at = np.array([s.arrival_s for s in specs])
    assert len(specs) == 100
    assert np.all(np.diff(at) > 0) and 0 < at[0] and at[-1] < 50
    gaps = np.diff(np.concatenate([[0.0], at]))
    # exponential gaps: coefficient of variation near 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15
    assert all(s.deadline_s is None for s in specs)


def test_rate_profile_bursts_keep_the_mean_rate():
    burst = dict(CHAT, rate_per_s=3.0, rate_profile=[[5, 3], [10, 0]],
                 schedule_seed=9)
    specs = TR.make(burst, 9, 45, 151552)
    at = np.array([s.arrival_s for s in specs])
    assert len(specs) == 135 and np.all(np.diff(at) >= 0)
    assert at[-1] < 45
    phase = np.mod(at, 15.0)
    assert np.all(phase < 5.0)            # none in the idle 10 s
    # each 5 s burst holds a third of the arrivals (9 per second)
    assert np.histogram(at, bins=[0, 15, 30, 45])[0].tolist() == \
        pytest.approx([45, 45, 45], abs=3)


def test_mixture_lengths_split_by_weight():
    mixed = {"dist": "mixture", "parts": [
        dict(CHAT["prompt"], weight=3),
        {"dist": "uniform", "min": 1024, "max": 1024, "weight": 1}]}
    v = TR.quantile_lengths(mixed, 100)
    assert (v == 1024).sum() >= 25 and len(v) == 100
    assert TR.longest(mixed) == 1024
    assert TR.prompt_cap(dict(CHAT, prompt=mixed)) == 1024


def test_backlog_shares_zipf_prefixes():
    specs = TR.make(BACKLOG, 5, 50, 151552)
    assert len(specs) == BACKLOG["backlog"]
    assert all(s.arrival_s == 0 and s.deadline_s == 50 for s in specs)
    counts = np.bincount([s.prefix_id for s in specs], minlength=8)
    assert counts.sum() == len(specs)
    assert list(counts) == sorted(counts, reverse=True)
    assert counts[0] / counts[1] == pytest.approx(2.0, rel=0.02)
    heads = {}
    for s in specs:
        assert 448 + 16 <= len(s.prompt) <= 448 + 64
        heads.setdefault(s.prefix_id, s.prompt[:448])
        assert np.array_equal(heads[s.prefix_id], s.prompt[:448])
    assert TR.prompt_cap(BACKLOG) == 512


def test_zipf_counts_sum_and_order():
    c = TR.zipf_counts(1000, 8, 1.0)
    assert c.sum() == 1000
    w = 1 / np.arange(1, 9)
    assert np.all(np.abs(c - 1000 * w / w.sum()) < 1)


def test_quantile_lengths_uniform_and_bounds():
    v = TR.quantile_lengths({"dist": "uniform", "min": 16, "max": 64}, 490)
    assert v.min() == 16 and v.max() == 64
    assert np.all(np.bincount(v)[16:65] == 10)
    with pytest.raises(ValueError):
        TR.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


def test_warm_prompts_share_no_prefix_with_the_window():
    specs = TR.make(CHAT, 3, 50, 151552)
    warm = TR.warm_prompts(3, [16, 32, 64], 151552)
    for w in warm:
        for s in specs:
            n = min(16, len(s.prompt))
            assert not np.array_equal(w[:n], s.prompt[:n])


def test_percentile_matches_numpy():
    from benchlib.stats import percentile

    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 10, 50, 90, 99, 100):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([], 50) is None
    assert math.isinf(percentile([1.0, float("inf")], 90))
    assert percentile([1.0, 2.0, float("inf")], 50) == 2.0
