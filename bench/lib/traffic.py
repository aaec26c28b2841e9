"""The one traffic generator: a backlog of requests from a mix's
parameters and the seed.

The backlog is made of blocks of ``max(16, n_slots)`` requests, and every
block holds the same set of prompt lengths and the same set of output
lengths (quantiles of the mix's clipped lognormal), each set in an order of
its own. The orders are the same for every seed, as in a replayed request
log: the engine's schedule depends only on the lengths, so every seed gets
the same schedule and the same amount of work in its window, and seeds
differ in the token ids (and the weights). A seed-drawn order would move the
95th percentile of the gaps from one prompt bucket's join stall to
another's. The first ``n_slots`` requests arrive one engine tick apart,
so the slot pool fills one request at a time; all later ones have arrived
once it is full, which keeps the backlog saturated.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def length_set(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a lognormal with
    the given median and sigma, clipped to ``[min, max]``."""
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec.get('dist')!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = [
        math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)
    ]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, stream])


def backlog(mix: Dict, n: int, n_slots: int, vocab: int, seed: int) -> List[Dict]:
    """``n`` requests: ``{"rid", "prompt" (int32 array), "max_new",
    "arrival"}``."""
    block = max(16, n_slots)
    reps = -(-n // block)
    rp, ro = rng_for(0, 1), rng_for(0, 2)
    pset, oset = length_set(mix["prompt"], block), length_set(mix["output"], block)
    plens = np.concatenate([rp.permutation(pset) for _ in range(reps)])
    olens = np.concatenate([ro.permutation(oset) for _ in range(reps)])
    toks = rng_for(seed, 3)
    return [
        {
            "rid": i,
            "prompt": toks.integers(0, vocab, int(plens[i]), dtype=np.int32),
            "max_new": int(olens[i]),
            "arrival": min(i, n_slots),
        }
        for i in range(n)
    ]
