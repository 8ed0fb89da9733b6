"""``iter_us.stream``: Device busy microseconds per APC iteration run (steps
x iters) in the closed stream.  The busy time is the whole window's: it
holds a step's passes outside the scan (the init's B b, the residual) and
the caller's own ``next_rhs`` pass over A.
"""
LAYER = "scan and engine: solvers/api.py, solvers/projection.py, kernels/"
MOVES = "lat_p95_ms"


def read(run):
    return run.iter_us()
