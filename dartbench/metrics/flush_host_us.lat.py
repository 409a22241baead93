"""Host microseconds per flush call, up to its return and before
completion: the mean of the benchmark's ``flush`` spans in the traced
stretch (around ``ga.flush()``, or around the engine's flush inside a
blocking op)."""


def read(run):
    return None if run.trace is None else run.trace.span_mean_us("flush")
