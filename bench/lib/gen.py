"""Traffic generation from a mix's parameters and a seed.

A traffic file's `kind` names the generator (GENERATORS). Every seed gets
the same multiset of sizes and inter-arrival gaps (drawn at evenly spaced
quantiles of the mix's distributions) in an order, and with token ids, of
its own: the seed changes which request is which, not how much work the
window holds. The order is balanced (`balanced_order`): every STRATA
consecutive requests hold one size from each STRATA-quantile of the mix,
so that a window which finishes only the first part of the schedule, as
one above the system's capacity does, still finishes the same work for
every seed.

A schedule is what the load generator (bench/lib/loadgen.py) sends:
{"mode": "open" | "closed", "requests": [{"prompt", "max_tokens", ...}],
 "clients": [[request index, ...], ...] (closed mode only),
 "prime": [prompt, ...] (prompts served once in set-up, before the window)}.
Open mode sends request i at its offset "t" (s) from the window's start;
closed mode runs each client's list in turn, the next request sent when
the last has finished, none after the window has closed.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> list[int]:
    """n sizes at the (i + 1/2)/n quantiles of lognormal(median, sigma),
    clipped to [lo, hi] and rounded."""
    nd = NormalDist()
    return [int(min(max(round(median * math.exp(sigma * nd.inv_cdf(
        (i + 0.5) / n))), lo), hi)) for i in range(n)]


def exponential_quantiles(n: int, mean: float) -> list[float]:
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


STRATA = 8


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def balanced_order(n: int, r: np.random.Generator) -> np.ndarray:
    """A permutation of range(n), the indices of n sorted sizes, drawn
    from `r`: the indices are cut into STRATA runs of neighbouring ranks,
    and round m of the order takes the m-th index of each run, in an order
    of its own."""
    runs = [r.permutation(s) for s in np.array_split(np.arange(n), STRATA)]
    order = []
    for m in range(len(runs[0])):
        order += list(r.permutation([x[m] for x in runs if m < len(x)]))
    return np.asarray(order, dtype=int)


def shuffled(values, r: np.random.Generator) -> np.ndarray:
    """Sorted `values` in a balanced order drawn from `r`."""
    return np.asarray(values)[balanced_order(len(values), r)]


def sizes(spec: dict, n: int) -> list[int]:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return lognormal_quantiles(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])


def residual_quantiles(lengths, n: int, lo: int) -> list[int]:
    """n sizes at the (i + 1/2)/n quantiles of the residual life of
    `lengths`: how much of a request is left at a random moment of a
    client that is always busy (length-biased, then a uniform point in
    it), clipped below at `lo`."""
    left = np.sort(np.concatenate([np.arange(1, int(x) + 1)
                                   for x in lengths]))
    idx = ((np.arange(n) + 0.5) / n * len(left)).astype(int)
    return [max(int(left[i]), lo) for i in idx]


def make(traffic: dict, seconds: float, seed: int, vocab: int) -> dict:
    """The schedule of the traffic file's `kind` for a window of
    `seconds`."""
    kind = traffic["kind"]
    if kind not in GENERATORS:
        raise ValueError(f"unknown traffic kind {kind!r}; have "
                         f"{sorted(GENERATORS)}")
    return GENERATORS[kind](traffic, seconds, seed, vocab)


def open_loop(traffic: dict, seconds: float, seed: int,
              vocab: int) -> dict:
    """Open-loop Poisson arrivals over a window of `seconds`: about
    rate x seconds requests, each {"t": send offset (s), "prompt": ids,
    "max_tokens": n}. Offsets are the permuted exponential-quantile gaps,
    scaled so the last request is due before the window closes."""
    rate = traffic["arrival"]["rate_rps"]
    n = max(int(round(rate * seconds)), 1)
    r = rng(seed, 0)
    gaps = shuffled(exponential_quantiles(n, 1.0 / rate), r)
    t = np.cumsum(gaps)
    t *= (seconds * n / (n + 1)) / t[-1]
    plens = shuffled(sizes(traffic["prompt_len"], n), r)
    olens = shuffled(sizes(traffic["output_len"], n), r)
    ids = rng(seed, 1)
    return {"mode": "open", "prime": [], "requests": [
        {"t": float(t[i]),
         "prompt": ids.integers(1, vocab, int(plens[i])).tolist(),
         "max_tokens": int(olens[i])} for i in range(n)]}


def closed_loop(traffic: dict, seconds: float, seed: int,
                vocab: int) -> dict:
    """`clients` users, each with `requests_per_client` requests sent one
    after another. Every prompt is a context of `shared_prefix` tokens
    common to all requests (drawn from the seed and served once in
    set-up), then a suffix of its own of `prompt_len` tokens. Each
    client's first output length is drawn from the residual life of the
    output lengths, so that completions are staggered from the start."""
    c, per = traffic["clients"], traffic["requests_per_client"]
    n = c * per
    r, ids = rng(seed, 0), rng(seed, 1)
    plens = shuffled(sizes(traffic["prompt_len"], n), r)
    out = traffic["output_len"]
    first = shuffled(residual_quantiles(sizes(out, n), c, out["min"]), r)
    rest = shuffled(sizes(out, n - c), r)
    olens = np.insert(rest, np.arange(c) * (per - 1), first)
    prefix = ids.integers(1, vocab, traffic["shared_prefix"]).tolist()
    reqs = [{"prompt": prefix + ids.integers(1, vocab, int(plens[i])).tolist(),
             "max_tokens": int(olens[i])} for i in range(n)]
    return {"mode": "closed", "prime": [prefix] if prefix else [],
            "requests": reqs,
            "clients": [list(range(k * per, (k + 1) * per))
                        for k in range(c)]}


GENERATORS = {"open_loop": open_loop, "closed_loop": closed_loop}
