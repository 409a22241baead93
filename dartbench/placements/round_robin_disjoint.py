"""Op ``i`` of an epoch to unit ``i mod units``, each in a disjoint slot
of that unit's window (a seeded slot, at a seeded offset inside it), as
an OSU bandwidth window spreads its messages."""

import numpy as np


def place(mix, lengths, k, units, elems, rng):
    n = lengths.size
    per_unit = -(-k // units)
    slot = elems // per_unit
    if lengths.max(initial=0) > slot:
        raise ValueError("an op is longer than its disjoint slot")
    i = np.arange(n) % k
    unit = i % units
    which = np.empty(n, np.int64)
    for s in range(0, n, k):
        order = np.concatenate([rng.permutation(per_unit)
                                for _ in range(units)])
        j = np.arange(min(k, n - s))
        which[s:s + j.size] = order[(j % units) * per_unit + j // units]
    lo = which * slot + (rng.random(n) * (slot - lengths + 1)).astype(
        np.int64)
    return unit, lo
