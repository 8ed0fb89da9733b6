"""``lat_p95_ms``: 95th percentile over every request of the window, from
its due time to its answer (a request never answered counts to the end of
the wait).
"""
import numpy as np


def read(run):
    if "latency_s" not in run.out:
        return None
    return 1e3 * float(np.percentile(run.out["latency_s"], 95))
