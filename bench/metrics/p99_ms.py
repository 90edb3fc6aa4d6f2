"""99th percentile (nearest rank), over every request due in the window, of
the time from when it was due to its answer. A request that was rejected,
shed, timed out or failed counts as infinite; an infinite percentile is
printed as ``INFINITE_MS``."""
import math

import numpy as np

from traffic import OK

INFINITE_MS = 1e12


def read(run):
    log = run.log
    due = np.flatnonzero(log.t_due <= run.t_end)
    if not len(due):
        return None
    lat = np.where(log.outcome[due] == OK,
                   log.t_done[due] - log.t_due[due], np.inf)
    v = float(np.sort(lat)[math.ceil(0.99 * len(lat)) - 1]) * 1e3
    return v if math.isfinite(v) else INFINITE_MS
