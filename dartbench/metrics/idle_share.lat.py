"""Percent of the traced stretch in which no operation ran on any device
of the run: 1 - busy / stretch, busy the union of device-operation
intervals over the cell's devices."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct()
