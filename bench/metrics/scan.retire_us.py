"""`scan.retire_us`: device microseconds per scan iteration in the
`tick.retire` scope: completions, timeouts and stale abandonment on the
window (`_retire_window`, `_complete_and_timeout`). Self time of the traced
slice's ops whose innermost tick scope is `tick.retire`, over the iterations
in the slice (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.scan_stage_us(sc, "tick.retire") if sc else None
