"""Host microseconds per ``put_nb`` call: the mean of the benchmark's
``enqueue`` spans in the traced stretch."""


def read(run):
    return None if run.trace is None else run.trace.span_mean_us("enqueue")
