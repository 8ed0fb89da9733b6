"""``iter_us.open``: Device busy microseconds per APC iteration run (batches
x iters).
"""
LAYER = "scan and engine: solvers/api.py, solvers/projection.py, kernels/"
MOVES = "lat_p95_ms"


def read(run):
    return run.iter_us()
