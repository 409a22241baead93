"""Payload bytes the window's ops asked to move (not the padding of
their dispatch buckets), over the window's seconds, in 1e9 bytes per
second (host clock).  A get's bytes count once its value is on the
host."""


def read(run):
    w = run.window
    if w.seconds <= 0:
        return None
    return float(run.traffic.epoch_bytes()[w.epoch].sum()) / w.seconds / 1e9
