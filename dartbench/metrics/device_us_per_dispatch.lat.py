"""Device busy microseconds per dispatch: the union of device-operation
intervals in the traced stretch over the engine's ``dispatch_count``
delta across that stretch."""


def read(run):
    if run.trace is None or not run.stretch["dispatches"]:
        return None
    busy = run.trace.busy_union_s()
    return busy / run.stretch["dispatches"] * 1e6 if busy > 0 else None
