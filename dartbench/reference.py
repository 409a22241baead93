"""The plain reference and the comparison that decides ``correct``.

:class:`Mirror` is a numpy model of the team windows, one row of
elements per unit (after the arena mirror of the repository's chip smoke
script, copied here so that the yardstick does not move with it).  It
imports nothing of the program.

:func:`compare` replays on the mirror, in issue order, every epoch a run
issued -- its warm-up and its window -- by each op kind's ``model``, and
holds the run to it: every value an op answered (a get's) against what
the model answers at that point of the replay, and
every unit's window after the run against the mirror's row.  All of it
is exact, so every limit is 0.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from .generator import Traffic

#: each number compared, and its limit (an exact comparison: 0)
LIMITS = {"wrong_get_elems": 0, "unanswered_gets": 0,
          "wrong_window_elems": 0, "window_compiles": 0}


class Mirror:
    """``units`` windows of ``elems`` elements of ``dtype``, zeroed."""

    def __init__(self, units: int, elems: int, dtype):
        self.rows = np.zeros((units, elems), dtype)

    def write(self, u: int, lo: int, values: np.ndarray) -> None:
        self.rows[u, lo:lo + values.size] = values

    def read(self, u: int, lo: int, n: int) -> np.ndarray:
        return self.rows[u, lo:lo + n].copy()


def _bits(a: np.ndarray) -> np.ndarray:
    """Bit patterns, so that the comparison is exact (and NaN-safe)."""
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def compare(traffic: Traffic, sequence: Sequence[int],
            values: Dict[int, List[Optional[np.ndarray]]],
            windows: np.ndarray, window_compiles: int) -> dict:
    """Replay ``sequence`` (epoch indices in issue order) and compare.

    Each op is applied to the mirror by its kind's ``model``.
    ``values[pos]`` holds the values returned by the epoch at position
    ``pos`` of the sequence, one per op (``None`` for an op that never
    answered), for a kind that ``READS``.  ``windows`` is the system's
    ``(units, elems)`` state after the run.  Returns ``{"numbers":
    {name: [value, limit]}, "correct": bool, "gets_compared": n,
    "failed_ops": n}`` (ops that answered wrong or never)."""
    units, elems = windows.shape
    mirror = Mirror(units, elems, traffic.dtype)
    rows = mirror.rows
    unit, lo, length = traffic.unit, traffic.lo, traffic.length
    wrong = wrong_ops = unanswered = compared = 0
    for pos, e in enumerate(sequence):
        op = traffic.op(e)
        got = values.get(pos)
        for j, i in enumerate(range(int(traffic.start[e]),
                                    int(traffic.start[e + 1]))):
            want = op.model(rows, int(unit[i]), int(lo[i]), int(length[i]),
                            traffic.payload(i) if op.PAYLOAD else None)
            if not op.READS:
                continue
            value = None if got is None else got[j]
            if value is None:
                unanswered += 1
                continue
            value = np.asarray(value, traffic.dtype).reshape(-1)
            want = np.asarray(want, traffic.dtype).reshape(-1)
            compared += 1
            bad = (int(want.size) if value.size != want.size else
                   int(np.count_nonzero(_bits(value) != _bits(want))))
            wrong += bad
            wrong_ops += bad > 0
    bad_window = 0
    for u in range(units):
        bad_window += int(np.count_nonzero(
            _bits(np.ascontiguousarray(windows[u])) != _bits(rows[u])))
    numbers = {"wrong_get_elems": wrong, "unanswered_gets": unanswered,
               "wrong_window_elems": bad_window,
               "window_compiles": int(window_compiles)}
    correct = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    return {"numbers": {k: [numbers[k], LIMITS[k]] for k in LIMITS},
            "correct": correct, "gets_compared": compared,
            "failed_ops": wrong_ops + unanswered}
