"""``autotune_s``: Seconds under the program's spans ``repro.ops.autotune``
in set-up: the candidate kernels the engine and tile autotune time when a
trace first meets a shape.  Self time, as a measurement may nest another
(an engine candidate picks its tiles).
"""
from bench.program_spans import seconds

LAYER = "set-up: LinsysServer.register and the first batch"
MOVES = "setup_s"


def read(run):
    return seconds("repro.ops.autotune", "self_s")
