"""``eig_s``: Seconds of the program's span ``repro.spectral.eig`` in
set-up: the host eigenvalue solve of Solver.analyze (the float64 X it
runs on is built in ``repro.spectral.x_matrix``, outside it).
"""
from bench.program_spans import seconds

LAYER = "set-up: core/spectral via Solver.analyze"
MOVES = "setup_s"


def read(run):
    return seconds("repro.spectral.eig")
