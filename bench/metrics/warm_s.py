"""``warm_s``: Host seconds of register (fingerprint) plus the first batches
(prepare, compile, autotune).
"""
LAYER = "set-up: LinsysServer.register and the first batch"
MOVES = "setup_s"


def read(run):
    return run.warm_s
