"""A get: the values of a range that an earlier put wrote, read back to
the host.  Blocking ``ga.at[u, lo:lo + n].get()`` with its device value
decoded to the host inside a ``decode`` span; non-blocking ``get_nb``,
its value read once the epoch has completed."""

import numpy as np

PAYLOAD = False
READS = True
RANGES = "written"
ENGINE_ENTRY = "get"
PLAN = "gather"


def issue(system, u, lo, n, payload, blocking):
    ref = system.ga.at[u, lo:lo + n]
    if not blocking:
        return ref.get_nb()
    value = ref.get()
    with system.span("decode"):
        return np.asarray(value)


def model(rows, u, lo, n, payload):
    return rows[u, lo:lo + n].copy()
