"""The one traffic generator: seeded i.i.d. Zipf id streams.

A traffic mix (``traffic/<mix>.json``) gives its parameters:

* ``batch``, ``seq``: the shape of one step's ``tokens`` (int32);
* ``alpha``: the Zipf exponent of the ids' marginal over ``n_ids`` (the
  configuration's table rows);
* ``pool``: how many distinct batches are made in set-up and cycled.

Semantics follow ``repro.data.ZipfLM`` with its bigram successors off (a
Zipf marginal over a seeded rank permutation), with the host cost taken
out of the run: the rank permutation is a seeded affine bijection of
[0, n_ids), and the whole pool is drawn before the first timed step."""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _affine(rng: np.random.Generator, n: int):
    while True:
        a = int(rng.integers(1, max(n, 2)))
        if math.gcd(a, n) == 1:
            return a, int(rng.integers(0, n))


def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-float(alpha))
    return np.cumsum(p / p.sum())


def batches(n_ids: int, mix: Dict, seed: int) -> List[Dict[str, np.ndarray]]:
    """The pool of ``mix['pool']`` batches for ``seed`` over ids [0, n_ids)."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    b, s = int(mix["batch"]), int(mix["seq"])
    cdf = zipf_cdf(n_ids, mix["alpha"])
    ra, rb = _affine(rng, n_ids)          # rank -> id

    def draw(shape):
        r = np.minimum(np.searchsorted(cdf, rng.random(shape)), n_ids - 1)
        return (r * ra + rb) % n_ids

    return [{"tokens": draw((b, s)).astype(np.int32)}
            for _ in range(int(mix["pool"]))]
