"""`scan.apply_us`: device microseconds per scan iteration in the
`tick.apply` scope: the decisions' state transition (`_apply_batch`). Self
time of the traced slice's ops whose innermost tick scope is `tick.apply`,
over the iterations in the slice (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.scan_stage_us(sc, "tick.apply") if sc else None
