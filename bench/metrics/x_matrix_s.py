"""``x_matrix_s``: Seconds of the program's span
``repro.spectral.x_matrix`` in set-up: the host float64 build of
X = (1/m) Σ A_iᵀ(A_iA_iᵀ)⁻¹A_i that Solver.analyze takes the eigenvalues
of (``eig_s``).
"""
from bench.program_spans import seconds

LAYER = "set-up: core/spectral via Solver.analyze"
MOVES = "setup_s"


def read(run):
    return seconds("repro.spectral.x_matrix")
