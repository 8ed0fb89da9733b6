"""``iter_roofline.open``: % of the least time per iteration (bench/work.py,
bench/peaks.json) in the device busy time per iteration.
"""
LAYER = "projection engine"
MOVES = "lat_p95_ms"


def read(run):
    return run.iter_roofline()
