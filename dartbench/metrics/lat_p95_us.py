"""95th percentile over every blocking op of the window of the host time
from the call until the op is complete, in microseconds (host clock)."""

import numpy as np


def read(run):
    if not run.traffic.blocking or not run.window.t0.size:
        return None
    return float(np.percentile(run.window.t1 - run.window.t0, 95)) * 1e6
