"""``fingerprint_s``: Seconds of the program's span
``repro.store.fingerprint`` in set-up: the copy of A to the host and its
sha256 at ``register``.
"""
from bench.program_spans import seconds

LAYER = "set-up: LinsysServer.register and the first batch"
MOVES = "setup_s"


def read(run):
    return seconds("repro.store.fingerprint")
