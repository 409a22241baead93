"""From a profiler trace to the numbers the per-layer readers take.

A traced run wraps a steady stretch of its window in a host span named
``stretch`` and each host step in a span of its own (``enqueue``,
``flush``, ``wait``, ``decode``; ``op`` around a whole blocking op).
:func:`load` reads the trace the JAX profiler wrote: the device
operations of every device plane and those host spans, on the trace's
one clock.  :class:`Trace` reduces them: device busy time as the union
of the operations' intervals, idle gaps labelled by the innermost host
span that covers them, and the breakdown of the result line.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

#: host spans the benchmark writes around the calls it makes
SPAN_NAMES = ("op", "enqueue", "flush", "wait", "decode")
STRETCH = "stretch"
#: the line of a device plane that holds one event per device operation
DEVICE_OP_LINE = "XLA Ops"
#: characters of a device operation's name kept in the breakdown (TPU
#: traces name an operation by its whole HLO instruction)
_NAME_CHARS = 160
#: spans searched back from a gap for one that covers it
_LOOK_BACK = 32
#: HLO opcodes that move data between devices; an operation is one of
#: them, or its async ``-start``/``-done`` half, by its instruction name
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")

Interval = Tuple[float, float]


def is_collective(name: str) -> bool:
    """Whether a device operation's name is a collective's: its
    instruction name (the leading word, past a ``%`` or ``_``, with
    ``_`` read as ``-``) starts with one of :data:`COLLECTIVES`.  An
    operation that only takes a collective's result as an operand reads
    as no collective."""
    head = name.lstrip("%_").replace("_", "-")
    return head.startswith(COLLECTIVES)


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches between merged busy intervals in ``[lo, hi]``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


@dataclasses.dataclass
class Trace:
    """One traced stretch, times in nanoseconds on the trace's clock.

    ``device_ops`` maps a device to its ``(name, start, end)`` operation
    events; ``spans`` are the host spans ``(name, start, end)``."""

    lo: float
    hi: float
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]]

    @property
    def stretch_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy(self, device: str) -> List[Interval]:
        return union([(s, e) for _, s, e in self.device_ops[device]],
                     self.lo, self.hi)

    def busy_union(self) -> List[Interval]:
        """Busy on any device of the run."""
        return union([(s, e) for ops in self.device_ops.values()
                      for _, s, e in ops], self.lo, self.hi)

    def busy_union_s(self) -> float:
        return total(self.busy_union()) * 1e-9

    def idle_pct(self) -> Optional[float]:
        """Percent of the stretch in which no operation ran on any device
        of the run; ``None`` where nothing ran at all."""
        busy = self.busy_union_s()
        if self.stretch_s <= 0 or busy <= 0:
            return None
        return 100 * (1 - busy / self.stretch_s)

    def busy_per_device_s(self) -> Dict[str, float]:
        return {d: total(self.busy(d)) * 1e-9 for d in self.device_ops}

    def busy_mean_s(self) -> float:
        per = self.busy_per_device_s()
        return sum(per.values()) / len(per) if per else 0.0

    def collective_mean_s(self) -> float:
        """Seconds in which a collective ran, the union over each
        device's collective operations, averaged over the devices of the
        run."""
        per = [total(union([(s, e) for n, s, e in ops if is_collective(n)],
                           self.lo, self.hi))
               for ops in self.device_ops.values()]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def span_durations_s(self, name: str) -> List[float]:
        return [(e - s) * 1e-9 for n, s, e in self.spans
                if n == name and s >= self.lo and e <= self.hi]

    def span_mean_us(self, name: str) -> Optional[float]:
        d = self.span_durations_s(name)
        return sum(d) / len(d) * 1e6 if d else None

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the device timeline (busy on any device),
        by the innermost host span that covers each gap's midpoint
        (``"none"`` where the benchmark was in none of its spans)."""
        spans = sorted(self.spans, key=lambda t: (t[1], -t[2]))
        starts = [a for _, a, _ in spans]
        out: Dict[str, float] = {}
        for s, e in gaps(self.busy_union(), self.lo, self.hi):
            mid = (s + e) / 2
            label = "none"
            # spans nest (``op`` holds the others), so the innermost span
            # covering ``mid`` is the latest-starting one that does
            i = bisect.bisect_right(starts, mid)
            for n, a, b in reversed(spans[max(0, i - _LOOK_BACK):i]):
                if b >= mid:
                    label = n
                    break
            out[label] = out.get(label, 0.0) + (e - s) * 1e-9
        return out

    def top_ops(self, n: int = 10) -> List[List]:
        """Device operations by total seconds in the stretch, summed over
        the devices of the run."""
        by: Dict[str, float] = {}
        for ops in self.device_ops.values():
            for name, s, e in ops:
                s, e = max(s, self.lo), min(e, self.hi)
                if e > s:
                    name = name[:_NAME_CHARS]
                    by[name] = by.get(name, 0.0) + (e - s) * 1e-9
        return [[k, v] for k, v in sorted(by.items(), key=lambda t: -t[1])
                ][:n]

    def breakdown(self) -> dict:
        idle = sorted(self.idle_by_span().items(), key=lambda t: -t[1])
        return {"device_ops": self.top_ops(),
                "idle_gaps": [[k, v] for k, v in idle][:10]}


def load(profile_dir: pathlib.Path) -> Trace:
    """Read the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {profile_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    stretch: Optional[Interval] = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == STRETCH:
                        stretch = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if stretch is None:
        raise ValueError("the trace holds no stretch span")
    return Trace(stretch[0], stretch[1], device_ops, spans)
