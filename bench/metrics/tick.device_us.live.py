"""`tick.device_us.live`: device microseconds per poll of the live session's
fused tick: the union of the device ops that start between a poll's
`session.dispatch` start and its `session.pull` end, averaged over the
traced slice's polls (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.device_us_live(sc) if sc else None
