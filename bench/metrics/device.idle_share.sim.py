"""`device.idle_share.sim`: share of the traced window of the scan
engine calls in which no operation ran on the device (bench/trace.py)."""


def read(ctx):
    t = ctx["trace"]
    return t["idle_share"] if t["n_device_ops"] else None
