"""Every op ``spec["elems"]`` elements long."""

import numpy as np


def draw(spec, n, per_block, rng):
    return np.full(n, int(spec["elems"]), np.int64)
