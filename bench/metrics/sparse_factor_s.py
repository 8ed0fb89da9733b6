"""``sparse_factor_s``: Seconds of the program's span
``repro.proj.sparse_factor`` in set-up: the host call that makes a sparse
operand's factors (support Gram, Cholesky, support pinv) at the factor
store's prepare.
"""
from bench.program_spans import seconds

LAYER = "set-up: sparse factors"
MOVES = "setup_s"


def read(run):
    return seconds("repro.proj.sparse_factor")
