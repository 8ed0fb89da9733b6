"""``compile_s``: Self seconds of the program's span
``repro.linsys.compile`` in set-up: the trace and compile of the serving
executors on their first call, the autotune inside them subtracted.
"""
from bench.program_spans import seconds

LAYER = "set-up: LinsysServer.register and the first batch"
MOVES = "setup_s"


def read(run):
    return seconds("repro.linsys.compile", "self_s")
