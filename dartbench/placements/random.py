"""Each op to a seeded unit, at any element offset its length fits."""

import numpy as np


def place(mix, lengths, k, units, elems, rng):
    n = lengths.size
    unit = rng.integers(0, units, n)
    lo = (rng.random(n) * (elems - lengths + 1)).astype(np.int64)
    return unit, lo
