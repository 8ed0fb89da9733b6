"""``batch_fill.open``: % of the batch slots run in the window that held a
real request: served / (served + padded).
"""
LAYER = "serving: solvers/pipeline.py"
MOVES = "lat_p95_ms"


def read(run):
    slots = run.stats["served"] + run.stats["padded"]
    return 100.0 * run.stats["served"] / slots if slots else None
