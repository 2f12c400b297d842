"""90th percentile of catalogue-write latency (add, remove or update
through the frontend, call to return) over every write of the window."""

import numpy as np


def read(run):
    if len(run.write_ms) == 0:
        return None
    return float(np.percentile(run.write_ms, 90))
