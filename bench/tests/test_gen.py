"""Traffic generation: reproducible from a seed, the same work for every
seed, sizes inside the mix's clips."""

from collections import Counter

from bench.lib import gen

CHAT = {"kind": "open_loop", "arrival": {"process": "poisson", "rate_rps": 2.0},
        "prompt_len": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                       "min": 16, "max": 768},
        "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                       "min": 16, "max": 384}}


def test_same_seed_same_schedule():
    a = gen.make(CHAT, 30.0, 2**31 + 99, 49152)["requests"]
    b = gen.make(CHAT, 30.0, 2**31 + 99, 49152)["requests"]
    assert a == b


def test_seeds_share_the_work_in_another_order():
    a = gen.make(CHAT, 30.0, 1, 49152)["requests"]
    b = gen.make(CHAT, 30.0, 2**33 + 5, 49152)["requests"]
    assert len(a) == len(b) == 60
    assert Counter(len(r["prompt"]) for r in a) == \
        Counter(len(r["prompt"]) for r in b)
    assert Counter(r["max_tokens"] for r in a) == \
        Counter(r["max_tokens"] for r in b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    def gaps(s):
        ts = [0.0] + [r["t"] for r in s]
        return sorted(round(y - x, 6) for x, y in zip(ts, ts[1:]))

    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert gaps(a) == gaps(b)


def test_arrivals_inside_the_window_and_ids_in_vocab():
    s = gen.make(CHAT, 51.0, 7, 49152)["requests"]
    ts = [r["t"] for r in s]
    assert ts == sorted(ts) and 0 < ts[0] and ts[-1] < 51.0
    assert all(1 <= t < 49152 for r in s for t in r["prompt"])


def test_lognormal_quantiles_clip_and_median():
    q = gen.lognormal_quantiles(101, 96, 0.8, 16, 768)
    assert min(q) >= 16 and max(q) <= 768
    assert q[50] == 96
    assert q == sorted(q)
    wide = gen.lognormal_quantiles(1000, 96, 3.0, 16, 768)
    assert wide[0] == 16 and wide[-1] == 768


def test_exponential_quantiles_mean():
    q = gen.exponential_quantiles(10000, 0.5)
    assert abs(sum(q) / len(q) - 0.5) < 0.01


REPO = {"kind": "closed_loop", "clients": 4, "requests_per_client": 5,
        "shared_prefix": 64,
        "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                       "min": 16, "max": 128},
        "output_len": {"dist": "lognormal", "median": 512, "sigma": 0.5,
                       "min": 128, "max": 896}}


def test_closed_loop_shares_the_prefix_and_the_work():
    a = gen.make(REPO, 30.0, 5, 49152)
    b = gen.make(REPO, 30.0, 2**32 + 1, 49152)
    assert a == gen.make(REPO, 30.0, 5, 49152)
    assert a["mode"] == "closed" and len(a["clients"]) == 4
    assert sorted(i for c in a["clients"] for i in c) == list(range(20))
    prefix = a["prime"][0]
    assert len(prefix) == 64 and prefix != b["prime"][0]
    for r in a["requests"]:
        assert r["prompt"][:64] == prefix and 16 <= len(r["prompt"]) - 64 <= 128
    for s in (a, b):    # first requests: the residual draw, the rest: sizes
        first = [s["requests"][c[0]]["max_tokens"] for c in s["clients"]]
        rest = [s["requests"][i]["max_tokens"] for c in s["clients"]
                for i in c[1:]]
        assert sorted(first) == gen.residual_quantiles(
            gen.sizes(REPO["output_len"], 20), 4, 128)
        assert sorted(rest) == sorted(gen.sizes(REPO["output_len"], 16))


def test_residual_life_of_equal_lengths_is_uniform():
    q = gen.residual_quantiles([100] * 7, 10, 1)
    assert all(abs(x - (10 * i + 5)) <= 1 for i, x in enumerate(q))
    # length-biased: a request 100x longer is ~99% of the time the one
    # in progress
    mixed = gen.residual_quantiles([10, 1000], 100, 1)
    assert sum(x > 10 for x in mixed) >= 97


def test_unknown_kind_is_refused():
    import pytest
    with pytest.raises(ValueError, match="unknown traffic kind"):
        gen.make(dict(REPO, kind="replay"), 30.0, 1, 100)


def test_balanced_order_rounds_hold_every_stratum():
    """Each run of STRATA consecutive requests holds one size from each
    STRATA-quantile, so any first part of a schedule holds the same mix."""
    import numpy as np
    n = 51
    strata = np.array_split(np.arange(n), gen.STRATA)
    which = {int(i): k for k, s in enumerate(strata) for i in s}
    rounds = n // gen.STRATA
    orders = [gen.balanced_order(n, gen.rng(seed, 0))
              for seed in (3, 2**31 + 11, 2**33 + 1)]
    for order in orders:
        assert sorted(order.tolist()) == list(range(n))
        for m in range(rounds):
            block = order[m * gen.STRATA:(m + 1) * gen.STRATA]
            assert sorted(which[int(i)] for i in block) == \
                list(range(gen.STRATA))
    assert orders[0].tolist() != orders[1].tolist()


def test_open_loop_first_rounds_share_the_work():
    """The output tokens of the first three rounds vary across 40 seeds by
    under 6% of the whole schedule's (a plain shuffle: about 13%)."""
    s = [gen.make(CHAT, 51.0, seed, 49152)["requests"]
         for seed in range(2**31, 2**31 + 40)]
    first = [sum(r["max_tokens"] for r in x[:3 * gen.STRATA]) for x in s]
    total = sum(r["max_tokens"] for r in s[0])
    assert max(first) - min(first) < 0.06 * total
