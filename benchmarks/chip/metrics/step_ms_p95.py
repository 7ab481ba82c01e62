"""95th percentile of the step time, over every step of the window: from
the first caller's region call to the last caller's rows ready (host
clock, milliseconds)."""
import numpy as np


def read(rec):
    if not rec["steps"]:
        return None
    return float(np.percentile([t1 - t0 for t0, t1, _ in rec["steps"]],
                               95)) * 1e3
