"""`session.put_us`: host microseconds per poll in the runtime's `DevicePut`
events (host-to-device argument transfers) inside the poll's
`session.dispatch` span, over the traced slice (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.put_us(sc) if sc else None
