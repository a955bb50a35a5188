"""Seeded traffic shared by the request-driven traffic kinds: the same multiset
of sizes and gaps for every seed, in another order.

A lognormal length at n evenly spaced quantiles, clipped, is the same
set of lengths whatever the seed; the seed only shuffles it and draws
the token ids.  So two seeds do the same work, and a difference
between their runs is noise, not a different load.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_set(n, spec):
    """n lengths: lognormal(median, sigma) at quantiles (k + 1/2)/n,
    rounded and clipped to [min, max]."""
    nd = NormalDist()
    mu = math.log(spec["median"])
    out = [math.exp(mu + spec["sigma"] * nd.inv_cdf((k + 0.5) / n))
           for k in range(n)]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n, rate):
    """n inter-arrival gaps of a Poisson process at ``rate`` per
    second, at quantiles (k + 1/2)/n."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def requests(n, spec, seed, vocab):
    """n (prompt, max_tokens) pairs: lengths from the fixed sets in a
    seeded order, token ids drawn from the seed."""
    rng = np.random.default_rng(seed)
    plens = rng.permutation(lognormal_set(n, spec["prompt"]))
    outs = rng.permutation(lognormal_set(n, spec["output"]))
    prompts = [rng.integers(0, vocab, (int(p),)).astype(np.int32)
               for p in plens]
    return prompts, [int(o) for o in outs]


def cycled_requests(set_size, spec, seed, vocab):
    """Endless (prompt, max_tokens) pairs whose sizes run through the
    same fixed set of ``set_size`` again and again, each pass in its own
    seeded order."""
    rng = np.random.default_rng(seed)
    pl = lognormal_set(set_size, spec["prompt"])
    ol = lognormal_set(set_size, spec["output"])
    while True:
        perm = rng.permutation(set_size)
        for p, o in zip(pl[perm], ol[perm]):
            yield (rng.integers(0, vocab, (int(p),)).astype(np.int32),
                   int(o))


def prompt_lengths(n, spec):
    """Every prompt length the mix can send (for warm-up)."""
    return sorted(set(int(x) for x in lognormal_set(n, spec["prompt"])))
