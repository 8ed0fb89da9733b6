"""``support_fill.stencil``: % of the entries the sparse operand stores
(m x p x the padded support width w) that are nonzero: the program's
counter ``BlockSystem.support_fill``.  None where the program keeps no such
counter.
"""
LAYER = "sparse operand: core/blockops.py"
MOVES = "lat_p95_ms"


def read(run):
    fill = getattr(run.problem.system, "support_fill", None)
    return None if fill is None else 100.0 * fill
