"""The tiny sizes the tests drive each cell at on the CPU."""

TINY = {
    "frontend.high_congestion": dict(
        config_overrides={"window": 64},
        traffic_overrides={"warm_polls": 5, "trace_seconds": 0.5}),
    "sim.high_congestion": dict(
        config_overrides={"n_ticks": 1500, "n_requests": 40,
                          "seeds_per_call": 2, "window": 64},
        traffic_overrides={"trace_offset_s": 0.0, "trace_seconds": 0.3}),
}

