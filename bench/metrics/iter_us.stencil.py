"""``iter_us.stencil``: Device busy microseconds per APC iteration run (steps
x iters) in the stencil's closed stream.  The busy time is the whole
window's: it holds a step's passes outside the scan (the init's B b, the
residual) and the caller's own ``next_rhs`` pass over A.
"""
LAYER = "sparse step: solvers/projection.py, kernels/ops.py, core/blockops.py"
MOVES = "lat_p95_ms"


def read(run):
    return run.iter_us()
