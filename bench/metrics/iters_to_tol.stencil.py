"""``iters_to_tol.stencil``: Mean of Served.iters_to_tol over the stencil's
closed-stream answers.
"""
LAYER = "solver (APC)"
MOVES = "lat_p95_ms"


def read(run):
    return run.iters_to_tol()
