"""``iters_to_tol.open``: Mean of Served.iters_to_tol over the window's
answers.
"""
LAYER = "solver (APC)"
MOVES = "lat_p95_ms"


def read(run):
    return run.iters_to_tol()
