"""``gen_late_ms.open``: 95th percentile of how late the generator submitted
a request after its due time.
"""
import numpy as np

LAYER = "load generator: bench/traffic"
MOVES = "lat_p95_ms"


def read(run):
    late = run.out.get("late_s")
    if late is None or not len(late):
        return None
    return 1e3 * float(np.percentile(late, 95))
