"""``iters_to_tol.stream``: Mean of Served.iters_to_tol over the closed
stream's answers.
"""
LAYER = "solver (APC)"
MOVES = "lat_p95_ms"


def read(run):
    return run.iters_to_tol()
