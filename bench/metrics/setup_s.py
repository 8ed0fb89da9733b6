"""``setup_s``: Process start to the first timed request: data from the
seed, analyze, register, the first batches.
"""


def read(run):
    return run.setup_s
