"""``wait_us.lat`` (``metrics/wait_us.lat.py``) of a cell on four chips,
which moves that cell's own latency metric."""

from dartbench import plugins

read = plugins.load("metrics", "wait_us.lat").read
