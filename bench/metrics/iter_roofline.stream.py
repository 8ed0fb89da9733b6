"""``iter_roofline.stream``: % of the least time per iteration at one
right-hand side (bench/work.py, bench/peaks.json) in the device busy time
per iteration.  The busy time is the whole window's: besides the scan it
holds a step's passes outside it (the init's B b, the residual) and the
caller's own ``next_rhs`` pass over A, which the least time leaves out.
"""
LAYER = "projection engine"
MOVES = "lat_p95_ms"


def read(run):
    return run.iter_roofline()
