"""Lengths log-uniform over ``[spec["min_elems"], spec["max_elems"]]``,
stratified per block: every block of ``per_block`` ops holds the
midpoints of ``per_block`` equal-probability strata, in a seeded order
(after the log-spaced size sweep of the repository's put/get benchmark,
drawn here instead of swept)."""

import math

import numpy as np


def draw(spec, n, per_block, rng):
    a, b = int(spec["min_elems"]), int(spec["max_elems"])
    per_block = max(1, per_block)
    q = (np.arange(per_block) + 0.5) / per_block
    strata = np.floor(np.exp(math.log(a) + q * (math.log(b + 1) - math.log(a))))
    strata = np.clip(strata.astype(np.int64), a, b)
    blocks = [rng.permutation(strata) for _ in range(-(-n // per_block))]
    return np.concatenate(blocks)[:n] if blocks else strata[:0]
