"""``idle_share.lat`` (``metrics/idle_share.lat.py``) of a cell on four chips,
which moves that cell's own latency metric."""

from dartbench import plugins

read = plugins.load("metrics", "idle_share.lat").read
