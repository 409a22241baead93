"""What the program itself totals over a traced stretch: the spans and
counters of ``repro.core.tracing``, which sum while a profiler session
is on.  The stretch is the run's one profiler session, so the totals
cover it exactly.  A program without that module reports nothing."""

from __future__ import annotations

from typing import Dict, Optional


def totals(run) -> Optional[Dict[str, dict]]:
    """The program's span totals of the traced stretch; ``None`` for an
    untraced run or a program that keeps none."""
    if run.trace is None:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.totals()


def span(run, name: str) -> Optional[dict]:
    """The totals of one span (``n``, ``s`` and its counters), or
    ``None`` where the stretch holds none."""
    t = totals(run)
    s = None if t is None else t.get(name)
    return s if s and s["n"] else None


def mean_us(run, name: str) -> Optional[float]:
    """Mean host microseconds of the span ``name`` in the stretch."""
    s = span(run, name)
    return None if s is None else s["s"] / s["n"] * 1e6
