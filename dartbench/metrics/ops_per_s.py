"""Ops completed in the window over the window's seconds (host clock):
the window runs from its first call to the completion of its last
epoch."""


def read(run):
    w = run.window
    if w.seconds <= 0:
        return None
    return float(run.traffic.epoch_ops()[w.epoch].sum()) / w.seconds
