"""Device microseconds of collectives per dispatch: on each device of
the run, the union of the intervals of its collective operations
(``all-reduce``, ``all-gather``, ``collective-permute``, ``all-to-all``,
``reduce-scatter`` and their ``-start``/``-done`` halves) in the traced
stretch, averaged over the devices, over the engine's
``dispatch_count`` delta across that stretch."""


def read(run):
    if run.trace is None or not run.stretch["dispatches"]:
        return None
    busy = run.trace.collective_mean_s()
    return busy / run.stretch["dispatches"] * 1e6 if busy > 0 else None
