"""``prepare_s``: Seconds of the program's span ``repro.store.prepare`` in
set-up: the factor store's miss, ``prepare`` and the kernel path's pinv
augmentation, up to the factors being ready on the device.
"""
from bench.program_spans import seconds

LAYER = "set-up: LinsysServer.register and the first batch"
MOVES = "setup_s"


def read(run):
    return seconds("repro.store.prepare")
