"""`scan.admit_us`: device microseconds per scan iteration in the
`tick.admit` scope: window compaction, admission of arrivals and the
admitted window's view (`_compact_and_admit`). Self time of the traced
slice's ops whose innermost tick scope is `tick.admit`, over the iterations
in the slice (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.scan_stage_us(sc, "tick.admit") if sc else None
