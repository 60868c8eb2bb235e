"""`tick.fetch_us`: host microseconds per poll in `session.pull` after the
runtime's notice that the poll's tick finished (its
`tpu::System::Execute=>Done` event; on the CPU backend the tick's last
op): the summary's device-to-host copy, its launch and the return to
Python.  Both ends are on the host's clock; averaged over the traced
slice's polls (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.fetch_us(sc) if sc else None
