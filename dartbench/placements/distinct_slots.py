"""Offsets aligned to the one fixed length, uniform over every unit's
window, no slot twice in an epoch (HPCC RandomAccess's updates in
flight land on distinct words)."""

import numpy as np


def place(mix, lengths, k, units, elems, rng):
    n = lengths.size
    size = int(lengths[0])
    if np.any(lengths != size):
        raise ValueError("distinct_slots needs one fixed length")
    per_unit = elems // size
    slots = np.empty(n, np.int64)
    for s in range(0, n, k):
        want = min(k, n - s)
        got = np.unique(rng.integers(0, units * per_unit, 2 * want))
        while got.size < want:
            got = np.unique(np.concatenate(
                [got, rng.integers(0, units * per_unit, want)]))
        slots[s:s + want] = rng.permutation(got)[:want]
    return slots // per_unit, (slots % per_unit) * size
