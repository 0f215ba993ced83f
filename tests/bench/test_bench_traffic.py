"""Traffic is a function of the seed: the same seed gives the same
requests, and every seed gets the same set of sizes and gaps."""
import json
import os

import numpy as np

from bench.traffic import closed_sessions, lengths, open_loop

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BIG = 2**31 + 977


def traffic(cell):
    with open(os.path.join(REPO, "bench", "traffic", f"{cell}.json")) as f:
        return json.load(f)


def test_open_loop_same_seed_same_schedule():
    t = traffic("starcoder2_3b-det.chat")
    a = open_loop.schedule(t, BIG, 30.0, 49152)
    b = open_loop.schedule(t, BIG, 30.0, 49152)
    assert len(a) == len(b) == int(np.ceil(t["rate_per_s"] * 30.0))
    for (da, pa, ma), (db, pb, mb) in zip(a, b):
        assert da == db and ma == mb and np.array_equal(pa, pb)


def test_open_loop_seeds_share_sizes_not_order():
    t = traffic("starcoder2_3b-det.chat")
    a = open_loop.schedule(t, 5, 30.0, 49152)
    b = open_loop.schedule(t, 6, 30.0, 49152)
    assert sorted(len(p) for _, p, _ in a) == sorted(len(p) for _, p, _ in b)
    assert sorted(m for *_, m in a) == sorted(m for *_, m in b)
    # the same gaps in another order: the spans differ by one gap at most
    gaps_a, gaps_b = np.diff([d for d, *_ in a]), np.diff([d for d, *_ in b])
    assert abs(gaps_a.sum() - gaps_b.sum()) <= max(gaps_a.max(), gaps_b.max())
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= len(p) <= hi for _, p, _ in a)
    assert all(1 <= int(p.min()) and int(p.max()) < 49152 for _, p, _ in a)
    # the offered rate is the file's: n requests over about n / rate seconds
    assert abs(a[-1][0] * t["rate_per_s"] - len(a)) < 0.1 * len(a)


def test_closed_sessions_by_seed():
    t = traffic("starcoder2_3b-det.long_decode")
    n = t["sessions"]

    def take(seed, k):
        it = closed_sessions.session_stream(t, seed, 49152)
        return [next(it) for _ in range(k)]

    a, b = take(BIG, n + 3), take(BIG, n + 3)
    assert all(np.array_equal(pa, pb) and ma == mb
               for (pa, ma), (pb, mb) in zip(a, b))
    assert all(len(p) == 2048 for p, _ in a)
    assert sorted(m for _, m in a[:n]) == sorted(
        m for _, m in take(7, n))
    assert all(1024 <= m <= 2048 for _, m in a)


def test_stratified_quantiles():
    rng = np.random.default_rng(0)
    v = lengths.stratified({"dist": "uniform", "min": 0, "max": 100}, 4, rng)
    assert sorted(v.tolist()) == [12, 38, 62, 88]
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.7,
            "min": 32, "max": 1024}
    assert lengths.quantile(spec, 0.5) == 512
    assert lengths.quantile(spec, 0.999) == 1024
