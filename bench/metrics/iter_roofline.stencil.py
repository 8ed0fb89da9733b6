"""``iter_roofline.stencil``: % of the least time of the window's
iterations in the device busy time.  One iteration's work is the
configuration's ``iteration_work`` (A by its nonzeros, each slab's support
pinv once, the iterates), not ``bench/work.py``'s dense count over the
padded support, so an operand stored more sparsely cannot read above 100%.
The busy time is the whole window's, as ``iter_us.stencil``'s.
"""
from bench import work

LAYER = "sparse projection engine"
MOVES = "lat_p95_ms"


def read(run):
    stats, trace = run.stats, run.trace
    if trace is None or run.peaks is None or not stats["batches"] \
            or not trace.busy_s:
        return None
    k = stats["served"] / stats["batches"]
    least = work.least_time(run.problem.iteration_work(k), run.peaks).seconds
    return 100.0 * least * run.iterations() / trace.chips / trace.busy_s
