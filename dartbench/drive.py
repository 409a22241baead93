"""Drive a system with a traffic: warm-up, the measured window, and the
host spans of a traced stretch.

Every epoch goes through :meth:`Driver.issue`, so warm-up, window and
the traced stretch issue ops by one code path.  The driver keeps the
order in which epochs were issued and every value a get returned; the
comparison with the reference reads both once the window has closed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .generator import Traffic

_NULL = contextlib.nullcontext()


def null_span(name: str):
    """The span of an untraced run: nothing."""
    return _NULL


def trace_span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """The epochs of one measured window, host-clock seconds."""

    begin: float
    t0: np.ndarray
    t1: np.ndarray
    epoch: np.ndarray
    #: positions ``[i0, i1)`` of the traced epochs, and the system's
    #: counters at both ends of the stretch
    stretch: Optional[tuple] = None
    stretch_counters: Optional[tuple] = None

    @property
    def seconds(self) -> float:
        return float(self.t1[-1] - self.begin) if self.t1.size else 0.0


class Tracer:
    """Profiles a stretch of the window: from ``skip_s`` into it, for
    ``span_s``, starting and stopping between epochs."""

    def __init__(self, log_dir: str, skip_s: float, span_s: float):
        self.log_dir = log_dir
        self.skip_s = skip_s
        self.span_s = span_s
        self._ann = None

    def begin(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("stretch")
        self._ann.__enter__()

    def end(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


class Driver:
    def __init__(self, system, traffic: Traffic,
                 span: Callable[[str], object] = null_span):
        self.system = system
        self.traffic = traffic
        self.span = span
        #: epoch indices in issue order, warm-up included
        self.sequence: List[int] = []
        #: position in ``sequence`` -> values of that epoch's ops, for an
        #: op kind that ``READS``
        self.values: Dict[int, List[np.ndarray]] = {}

    def issue(self, e: int) -> None:
        t, sys, span = self.traffic, self.system, self.span
        s, f = int(t.start[e]), int(t.start[e + 1])
        op = t.op(e)
        pos = len(self.sequence)
        self.sequence.append(e)
        args = [(int(t.unit[i]), int(t.lo[i]), int(t.length[i]),
                 t.payload(i) if op.PAYLOAD else None) for i in range(s, f)]
        if t.blocking:
            with span("op"):
                value = sys.issue(op, *args[0], True)
            if op.READS:
                self.values[pos] = [value]
            return
        handles = []
        for a in args:
            with span("enqueue"):
                handles.append(sys.issue(op, *a, False))
        with span("flush"):
            sys.flush()
        with span("wait"):
            sys.complete(handles)
        if op.READS:
            values = []
            for h in handles:
                with span("decode"):
                    values.append(sys.value(h))
            self.values[pos] = values

    def warm_up(self) -> None:
        """Issue the epochs that cover every dispatch shape of the
        traffic (:meth:`Traffic.warmup_epochs`)."""
        for e in self.traffic.warmup_epochs():
            self.issue(e)

    def window(self, seconds: float, tracer: Optional[Tracer] = None
               ) -> Window:
        """Issue epochs, cycling over the traffic, until ``seconds`` have
        passed and a whole kind cycle (``Traffic.period``) is done; an
        epoch started before then runs to its end.  A traced stretch
        lasts ``tracer.span_s`` and holds at least one epoch of every
        kind; the window does not close before its stretch has begun,
        even where one epoch outlasts ``seconds``."""
        t = self.traffic
        n, period = t.n_epochs, t.period
        kinds_all = set(np.unique(t.kind).tolist())
        t0: List[float] = []
        t1: List[float] = []
        epochs: List[int] = []
        stretch = counters = None
        state = "before" if tracer is not None else "off"
        clock = time.perf_counter
        k = 0
        begin = clock()
        deadline = begin + seconds
        while True:
            now = clock()
            if now >= deadline and k % period == 0 and state != "before":
                break
            if state == "before" and now - begin >= tracer.skip_s:
                tracer.begin()
                i0, c0, traced_at = len(t0), self.system.counters(), clock()
                seen = set()
                state = "in"
            elif (state == "in" and now - traced_at >= tracer.span_s
                  and seen == kinds_all):
                c1 = self.system.counters()
                tracer.end()
                stretch, counters = (i0, len(t0)), (c0, c1)
                state = "done"
            e = k % n
            a = clock()
            self.issue(e)
            t0.append(a)
            t1.append(clock())
            epochs.append(e)
            if state == "in":
                seen.add(int(t.kind[e]))
            k += 1
        if state == "in":
            c1 = self.system.counters()
            tracer.end()
            stretch, counters = (i0, len(t0)), (c0, c1)
        return Window(begin, np.asarray(t0), np.asarray(t1),
                      np.asarray(epochs, np.int64), stretch, counters)
