"""`scan.order_us`: device microseconds per scan iteration in the
`tick.order` scope: eligibility and the ranked candidates (`select_top_b`,
`rank_fifo`) of `schedule_batch`. Self time of the traced slice's ops whose
innermost tick scope is `tick.order`, over the iterations in the slice
(bench/scopes.py)."""
from bench import scopes


def read(ctx):
    sc = scopes.of(ctx)
    return scopes.scan_stage_us(sc, "tick.order") if sc else None
