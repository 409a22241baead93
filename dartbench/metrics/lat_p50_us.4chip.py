"""``lat_p50_us`` (``metrics/lat_p50_us.py``) of a cell on four chips: the
same reading under a bound of its own, fitted to that cell's spread."""

from dartbench import plugins

read = plugins.load("metrics", "lat_p50_us").read
