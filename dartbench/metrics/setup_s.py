"""Seconds from process start to the first timed op: loading, the
runtime's set-up, drawing the traffic and the warm-up, compilation
included (host clock)."""


def read(run):
    return run.setup_s
