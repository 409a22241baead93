"""``device_us_per_dispatch.lat`` (``metrics/device_us_per_dispatch.lat.py``) of a cell on four chips,
which moves that cell's own latency metric."""

from dartbench import plugins

read = plugins.load("metrics", "device_us_per_dispatch.lat").read
