"""``analyze_s``: Host seconds of the program's Solver.analyze (gamma and
eta).
"""
LAYER = "set-up: core/spectral via Solver.analyze"
MOVES = "setup_s"


def read(run):
    return run.analyze_s
