"""``idle_share.stencil``: % of the traced window with no op on the device:
the caller's gap between steps (next right-hand side, submit, fetch).
"""
LAYER = "device"
MOVES = "lat_p95_ms"


def read(run):
    return run.idle_share()
