"""Sizes and arrivals that every seed shares, in an order the seed picks.

A set of ``n`` lengths is the lognormal's quantiles at ``(i + 0.5) / n``
(clipped and rounded), so two seeds send the same work; the seed only
permutes it and draws the token ids.  That keeps a run's amount of work
fixed from seed to seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict

import numpy as np


def lognormal_set(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths, median ``spec['median']``, log-sd ``spec['sigma']``,
    clipped to ``[spec['min'], spec['max']]``, ascending."""
    z = NormalDist()
    out = [spec["median"] * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, total: float) -> np.ndarray:
    """``n`` gaps at the exponential's quantiles, scaled to sum to
    ``total``, ascending."""
    q = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return q * (total / q.sum())


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use, from any non-negative seed."""
    return np.random.default_rng([int(seed), stream])


def token_ids(r: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """Uniform ids over the vocabulary, above the three special ids the
    program reserves (pad, bos, eos)."""
    return r.integers(3, vocab, size=n).astype(np.int32)
