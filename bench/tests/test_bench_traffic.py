"""The traffic generator and the percentile arithmetic."""

import math

import numpy as np

from bench import traffic

MIXED = {"driver": "serve_open", "rate_rps": 10.0,
         "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                    "min": 32, "max": 896},
         "output": {"dist": "uniform", "min": 16, "max": 128}}
CLOSED = {"driver": "serve_closed", "clients": 4, "pool": 200,
          "prompt": {"dist": "uniform", "min": 32, "max": 128},
          "output": {"dist": "uniform", "min": 384, "max": 768}}


def test_same_seed_same_requests():
    a = traffic.requests(MIXED, 2 ** 40 + 3, 5.0, 8000)
    b = traffic.requests(MIXED, 2 ** 40 + 3, 5.0, 8000)
    assert [r.t for r in a] == [r.t for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]


def test_seeds_draw_tokens_on_one_schedule():
    a = traffic.requests(MIXED, 1, 30.0, 8000)
    b = traffic.requests(MIXED, 2, 30.0, 8000)
    assert len(a) == len(b) == 300
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.t for r in a] == [r.t for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.requests(dict(MIXED, order_seed=5), 1, 30.0, 8000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    reqs = traffic.requests(MIXED, 9, 20.0, 8000)
    t = np.array([r.t for r in reqs])
    assert len(reqs) == 200
    assert np.all(np.diff(t) > 0) and 0 < t[0] and t[-1] < 20.0
    gaps = np.diff(np.concatenate([[0.0], t]))
    assert abs(gaps.mean() - 0.1) < 0.005          # Poisson at 10 req/s
    assert 0.9 < gaps.std() / gaps.mean() < 1.05   # exponential: CV ~ 1


def test_length_distributions():
    reqs = traffic.requests(MIXED, 4, 100.0, 8000)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= 32 and p.max() <= 896
    assert abs(np.median(p) - 256) <= 2
    # lognormal sigma 0.8: log-lengths' interquartile range 2 x 0.674 x 0.8
    q1, q3 = np.percentile(np.log(p), [25, 75])
    assert abs((q3 - q1) - 2 * 0.6745 * 0.8) < 0.03
    assert o.min() == 16 and o.max() == 128
    assert abs(o.mean() - 72) < 1
    c = traffic.requests(CLOSED, 4, 10.0, 8000)
    assert len(c) == 200 and all(r.t == 0.0 for r in c)
    assert min(r.max_new for r in c) == 384
    assert max(r.max_new for r in c) == 768


def test_corpus_tokens_are_in_vocab_and_seeded():
    t = traffic.corpus_tokens(7, 3, 500, 8000)
    assert t.dtype == np.int32 and len(t) == 500 and t[0] == 1
    assert t[1:].min() >= 2 and t.max() < 8000
    assert np.array_equal(t, traffic.corpus_tokens(7, 3, 500, 8000))
    assert not np.array_equal(t, traffic.corpus_tokens(8, 3, 500, 8000))


def test_percentile_counts_failures_as_late():
    vals = [float(i) for i in range(1, 101)]
    assert traffic.percentile(vals, 95) == 95.0
    assert traffic.percentile(vals, 50) == 50.0
    late = vals[:94] + [math.inf] * 6
    assert traffic.percentile(late, 95) == math.inf
    assert traffic.percentile(vals[:95] + [math.inf] * 5, 95) == 95.0
    assert math.isnan(traffic.percentile([], 95))
