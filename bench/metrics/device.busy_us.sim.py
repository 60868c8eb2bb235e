"""`device.busy_us.sim`: device busy microseconds per scan iteration of the
engine (one tick of the call's vmapped seeds): the traced slice's busy
time over the iterations in it, counted by how often one `tick.retire`
op ran (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    n = scopes.iterations(sc) if sc else 0
    return ctx["trace"]["busy_s"] * 1e6 / n if n else None
