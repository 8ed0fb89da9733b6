"""``idle_share.open``: % of the traced window with no op on the device.
"""
LAYER = "device"
MOVES = "lat_p95_ms"


def read(run):
    return run.idle_share()
