"""``flush_host_us.lat`` (``metrics/flush_host_us.lat.py``) of a cell on four chips,
which moves that cell's own latency metric."""

from dartbench import plugins

read = plugins.load("metrics", "flush_host_us.lat").read
