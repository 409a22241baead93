"""The one traffic generator: a mix's parameters + a deployment + a seed
-> every epoch of one-sided ops a run may issue, drawn up front on the
host so that drawing is never timed.

A mix is a data file ``traffic/<mix>.json`` with these keys; the op
kinds, length distributions and placements it names are parts found by
name (:mod:`.plugins`):

``epoch_ops``
    ops per epoch.  An epoch is one coalescing unit of one op kind: its
    ops are issued, then flushed once and completed once.
``blocking``
    ``true``: every epoch is one blocking op (a closed loop of one
    caller, ``epoch_ops`` must be 1); ``false``: non-blocking ops
    followed by one flush and completion.
``kinds``, ``kind_order``, ``block_epochs``
    the op kinds (``ops/<kind>.py``) and their ratio (``{"put": 2,
    "get": 1}``), and how they are ordered: ``"shuffle"`` permutes each
    block of ``block_epochs`` epochs with the seed (the first epoch of a
    run is always of a drawn kind), ``"cycle"`` repeats the ratio in the
    order written (the first kind written has to be a drawn one).
``length``
    op lengths in elements of the deployment's ``dtype``:
    ``{"dist": <lengths/<dist>.py>, ...its parameters}``, drawn per block
    of a drawn kind's ops.
``placement``
    where a drawn op lands: ``placements/<placement>.py``.
``lookback``
    an epoch of a ``"written"`` kind (a get) takes the ranges of a
    seeded one of the last ``lookback`` drawn epochs, in a seeded order,
    so that it reads bytes the run wrote.
``epochs``
    epochs drawn; a window that outruns them starts over from the first.

A window ends on a whole cycle of ``"cycle"`` kinds (after a get window
where put and get windows alternate), so that its rate never depends on
where in the cycle the clock ran out.

The same seed gives the same traffic.  Seeds may be any non-negative
whole number (``numpy`` seeds take arbitrarily large integers).
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from . import plugins


@dataclasses.dataclass
class Traffic:
    """Every epoch of a run, as flat op arrays.

    Epoch ``e`` is ops ``start[e]:start[e + 1]`` of kind
    ``kind_names[kind[e]]``.  Op ``i`` addresses ``length[i]`` elements
    at element ``lo[i]`` of unit ``unit[i]``'s window; its payload, for a
    kind that carries one, is ``pool[pstart[i]:pstart[i] + length[i]]``."""

    blocking: bool
    #: epochs of one kind cycle: a window ends on a multiple of it
    period: int
    kind_names: Tuple[str, ...]
    kind: np.ndarray
    start: np.ndarray
    unit: np.ndarray
    lo: np.ndarray
    length: np.ndarray
    pstart: np.ndarray
    pool: np.ndarray
    dtype: np.dtype

    @property
    def n_epochs(self) -> int:
        return int(self.kind.size)

    @property
    def ops(self) -> list:
        """The op-kind modules, indexed as ``kind``."""
        return [plugins.load("ops", n) for n in self.kind_names]

    def op(self, e: int):
        """The op-kind module of epoch ``e``."""
        return plugins.load("ops", self.kind_names[int(self.kind[e])])

    def is_kind(self, name: str) -> np.ndarray:
        """Per epoch: whether it is of kind ``name``."""
        if name not in self.kind_names:
            return np.zeros(self.n_epochs, bool)
        return self.kind == self.kind_names.index(name)

    def ops_of(self, e: int) -> slice:
        return slice(int(self.start[e]), int(self.start[e + 1]))

    def payload(self, i: int) -> np.ndarray:
        s = int(self.pstart[i])
        return self.pool[s:s + int(self.length[i])]

    def epoch_ops(self) -> np.ndarray:
        """Ops of each epoch."""
        return np.diff(self.start)

    def epoch_bytes(self) -> np.ndarray:
        """Payload bytes each epoch asks to move."""
        return (np.add.reduceat(self.length, self.start[:-1])
                * self.dtype.itemsize)

    def max_len(self) -> np.ndarray:
        """The largest op of each epoch, in elements."""
        return np.maximum.reduceat(self.length, self.start[:-1])

    def warmup_epochs(self) -> List[int]:
        """The epochs a window's dispatch shapes are warmed with: for
        each signature -- (kind, ops, power-of-two octave of the largest
        op's bytes) -- the epochs with the largest and the smallest
        largest op.  Epochs of one signature share their dispatch shapes
        under any bucketing whose bucket edges are powers of two."""
        top = self.max_len()
        nbytes = top * self.dtype.itemsize
        octave = np.ceil(np.log2(np.maximum(nbytes, 1))).astype(np.int64)
        sig = np.stack([self.kind.astype(np.int64), np.diff(self.start),
                        octave], axis=1)
        _, group = np.unique(sig, axis=0, return_inverse=True)
        group = group.ravel()
        out = set()
        for g in range(int(group.max()) + 1):
            members = np.flatnonzero(group == g)
            out.add(int(members[np.argmax(top[members])]))
            out.add(int(members[np.argmin(top[members])]))
        return sorted(out)


def _kinds(mix: dict, drawn: np.ndarray, n_epochs: int,
           rng: np.random.Generator) -> np.ndarray:
    pattern = [i for i, r in enumerate(mix["kinds"].values())
               for _ in range(int(r))]
    if not pattern or not any(drawn[pattern]):
        raise ValueError("a mix needs a kind whose ranges are drawn")
    if mix.get("kind_order", "cycle") == "cycle":
        if not drawn[pattern[0]]:
            raise ValueError("a cycle starts with a kind whose ranges "
                             "are drawn")
        return np.resize(np.asarray(pattern, np.int8), n_epochs)
    block = int(mix["block_epochs"])
    if block % len(pattern):
        raise ValueError("block_epochs must be a multiple of the kind ratio")
    base = np.resize(np.asarray(pattern, np.int8), block)
    kinds = np.concatenate([rng.permutation(base)
                            for _ in range(-(-n_epochs // block))])[:n_epochs]
    first = int(np.argmax(drawn[kinds]))
    kinds[[0, first]] = kinds[[first, 0]]
    return kinds


def generate(mix: dict, config: dict, seed: int) -> Traffic:
    """Draw every epoch of a run of ``mix`` on deployment ``config``."""
    rng = np.random.default_rng(int(seed))
    k = int(mix["epoch_ops"])
    blocking = bool(mix["blocking"])
    if blocking and k != 1:
        raise ValueError("a blocking mix issues one op per epoch")
    n_epochs = int(mix["epochs"])
    dtype = np.dtype(config["dtype"])
    units = int(config["units"])
    elems = int(config["window_bytes_per_unit"]) // dtype.itemsize
    names = tuple(mix["kinds"])
    ops = [plugins.load("ops", n) for n in names]
    for name, op in zip(names, ops):
        if op.RANGES not in ("drawn", "written"):
            raise ValueError(f"op kind {name!r}: RANGES is {op.RANGES!r}")
    drawn = np.array([op.RANGES == "drawn" for op in ops])
    kinds = _kinds(mix, drawn, n_epochs, rng)
    drawn_e = np.flatnonzero(drawn[kinds])
    block = int(mix.get("block_epochs", 1))
    per_block = max(1, round(block * float(np.mean(drawn[kinds])))) * k
    lengths = plugins.load("lengths", mix["length"]["dist"])
    place = plugins.load("placements", mix["placement"])
    d_len = lengths.draw(mix["length"], drawn_e.size * k, per_block, rng)
    d_unit, d_lo = place.place(mix, d_len, k, units, elems, rng)

    unit = np.empty(n_epochs * k, np.int64)
    lo = np.empty_like(unit)
    length = np.empty_like(unit)
    rows = (drawn_e[:, None] * k + np.arange(k)).ravel()
    unit[rows], lo[rows], length[rows] = d_unit, d_lo, d_len

    written_e = np.flatnonzero(~drawn[kinds])
    if written_e.size:
        lookback = int(mix["lookback"])
        before = np.searchsorted(drawn_e, written_e)
        back = (rng.random(written_e.size) * np.minimum(before, lookback)
                ).astype(np.int64)
        src = drawn_e[before - 1 - back]
        order = np.argsort(rng.random((written_e.size, k)), axis=1)
        src_rows = (src[:, None] * k + order).ravel()
        dst_rows = (written_e[:, None] * k + np.arange(k)).ravel()
        unit[dst_rows] = unit[src_rows]
        lo[dst_rows] = lo[src_rows]
        length[dst_rows] = length[src_rows]

    max_len = int(length.max())
    pool = rng.standard_normal(max(4 * max_len, 1 << 20)).astype(dtype)
    pstart = (rng.random(unit.size) * (pool.size - length + 1)).astype(
        np.int64)
    carries = np.array([bool(op.PAYLOAD) for op in ops])
    pstart[np.repeat(~carries[kinds], k)] = 0
    period = (sum(int(v) for v in mix["kinds"].values())
              if mix.get("kind_order", "cycle") == "cycle" else 1)
    return Traffic(blocking=blocking, period=period, kind_names=names,
                   kind=kinds,
                   start=np.arange(n_epochs + 1, dtype=np.int64) * k,
                   unit=unit, lo=lo, length=length, pstart=pstart,
                   pool=pool, dtype=dtype)
