"""Named host spans and counters inside the runtime, switched by the JAX
profiler.

``span(name, **counts)`` marks one step of the host path (docs/API.md
"Observability" lists the names).  The profiler is the switch; there is
no setting of our own:

* **off** (no profiler session): ``span`` returns one shared no-op
  object (:data:`OFF`) whose ``add`` does nothing -- one C call and no
  span object;
* **on**: the span is a ``jax.profiler.TraceAnnotation``, so it lands in
  the trace on the device timeline's clock, nested under the enclosing
  span of its thread, with its counters as event stats.  On exit its
  duration and counters are added to a per-name total.

``totals()`` holds those totals for the latest profiler session: the
first span that finds tracing on after a span that found it off, or in
another ``jax.profiler.start_trace`` session, starts them afresh.  The
totals are process-wide, as the profiler session they follow is.
"""

from __future__ import annotations

import threading
import time
from typing import Dict

from jax._src import profiler as _jax_profiler
from jax.profiler import TraceAnnotation

_enabled = TraceAnnotation.is_enabled
#: JAX's python-side profiler state: its ``profile_session`` names the
#: ``start_trace`` session a span runs in (``None`` under a remote
#: capture, which only the off-to-on rule can tell apart)
_PROFILE_STATE = getattr(_jax_profiler, "_profile_state", None)

_LOCK = threading.Lock()
_TOTALS: Dict[str, Dict[str, float]] = {}
#: the session ``_TOTALS`` belong to; ``None`` once a span found tracing
#: off, so the next traced span starts the totals afresh
_session = None
_REMOTE = object()


class _Off:
    """The span of an untraced call: does nothing."""

    __slots__ = ()
    on = False

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def add(self, **counts) -> None:
        pass


OFF = _Off()


class _Span:
    """One traced step: a ``TraceAnnotation`` that adds its duration and
    counters to the totals on exit."""

    __slots__ = ("name", "counts", "_ann", "_t0")
    on = True

    def __init__(self, name: str, counts: Dict[str, int]):
        self.name = name
        self.counts = counts

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.counts)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def add(self, **counts) -> None:
        """Counters known only after the body started."""
        self._ann.set_metadata(**counts)
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + v

    def __exit__(self, *exc) -> None:
        ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        with _LOCK:
            t = _TOTALS.get(self.name)
            if t is None:
                t = _TOTALS[self.name] = {"n": 0, "s": 0.0}
            t["n"] += 1
            t["s"] += ns * 1e-9
            for k, v in self.counts.items():
                t[k] = t.get(k, 0) + v


def span(name: str, **counts):
    """A context manager around one step of the host path; ``counts``
    are its counters (ints), summed into ``totals()``.  Guard counters
    that cost work to compute with ``if s.on:`` and pass them to
    ``s.add``."""
    global _session
    if not _enabled():
        _session = None
        return OFF
    sess = (_PROFILE_STATE.profile_session if _PROFILE_STATE is not None
            else None) or _REMOTE
    if sess is not _session:
        with _LOCK:
            if sess is not _session:
                _TOTALS.clear()
                _session = sess
    return _Span(name, counts)


def totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"n": spans, "s": seconds, <counter>: sum}}`` of the
    latest profiler session."""
    with _LOCK:
        return {name: dict(t) for name, t in _TOTALS.items()}


def reset() -> None:
    """Forget the totals."""
    with _LOCK:
        _TOTALS.clear()
