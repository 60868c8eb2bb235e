"""`scan.grant_us`: device microseconds per scan iteration in the
`tick.grant` scope: the per-grant loop of `schedule_batch`: DRR allocation
and the admission ladder. Self time of the traced slice's ops whose
innermost tick scope is `tick.grant`, over the iterations in the slice
(bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.scan_stage_us(sc, "tick.grant") if sc else None
