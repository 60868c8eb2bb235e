"""Entry for the simulator: `run_scenario_cell` on the windowed engine.

Set-up compiles the cell's program (the jitted horizon, and the
seed-key construction around it) by making one call.
The window then runs whole calls back to back, each with fresh seeds
derived from `--seed` and the call index, until `--seconds` have
passed; the rate is all their simulated ticks (ticks x vmapped seeds)
over all their time.  Once the window has closed, the plain reference
(bench/reference/scan_ref.py) recomputes one call's seeds, drawn from
`--seed`, and the per-seed outcomes are compared.
"""
from __future__ import annotations

import time

import numpy as np

from bench.entries.session import _policy


def seed0(seed: int, call: int) -> int:
    """First PRNG seed of window call `call`: a 31-bit value derived from
    the run's seed, so that `seed0 + seeds` stays a valid int32."""
    state = np.random.SeedSequence([int(seed), int(call)]).generate_state(1)
    return int(state[0] % (2**31 - 1024))


def outcomes(m, pm, n: int) -> dict:
    """Per-seed counts and tails from the program's metrics (`n`
    requests a seed; rates are over the accepted, i.e. unrejected)."""
    m = {k: np.asarray(v) for k, v in m._asdict().items()}
    completed = np.asarray(pm.n_completed).sum(axis=-1)
    rejected = m["n_rejects"].astype(np.int64)
    accepted = np.maximum(n - rejected, 1)
    return {
        "completed": completed, "rejected": rejected,
        "abandoned": m["n_abandoned"].astype(np.int64),
        "met": np.rint(m["satisfaction"].astype(np.float64) * accepted
                       ).astype(np.int64),
        "defer_events": m["n_defer_events"].astype(np.int64),
        "short_p95_ms": m["short_p95_ms"], "global_p95_ms": m["global_p95_ms"],
        "tail_ema": m["mean_severity_proxy"],
    }


COUNTS = ("completed", "rejected", "abandoned", "met", "defer_events")
TAILS = ("short_p95_ms", "global_p95_ms")


def _rel_gap(a, b) -> float:
    """Widest |a - b| / max(|b|, 1) over seeds; two NaNs (a percentile
    of an empty set) agree, one NaN is an infinite gap."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    rel = np.abs(a - b) / np.maximum(np.abs(b), 1.0)
    rel = np.where(np.isnan(a) & np.isnan(b), 0.0,
                   np.where(np.isnan(rel), np.inf, rel))
    return float(rel.max())


def compare(prog: dict, ref: dict) -> dict:
    """`outcome_gap`: the widest per-seed difference in any count, in
    requests; `latency_gap`: the widest relative difference of a
    95th-percentile latency; `ema_gap`: the widest relative difference of
    the final tail EMA, the one float every decision's severity fed on."""
    outcome = max(int(np.abs(np.asarray(prog[k], np.int64)
                             - np.asarray(ref[k], np.int64)).max())
                  for k in COUNTS)
    return {"outcome_gap": outcome,
            "latency_gap": max(_rel_gap(prog[k], ref[k]) for k in TAILS),
            "ema_gap": _rel_gap(prog["tail_ema"], ref["tail_ema"])}


def run(cell) -> dict:
    import jax

    from repro.sim import SimConfig, run_scenario_cell
    from repro.sim.provider import default_physics

    cfg, tr = cell.config, cell.traffic
    policy = _policy(cfg)
    phys = default_physics(**cfg["provider"])
    sim_cfg = SimConfig(dt_ms=float(cfg["dt_ms"]), n_ticks=int(cfg["n_ticks"]),
                        k_slots=int(cfg["k_slots"]),
                        ordering_backend=cfg["backend"],
                        window=int(cfg["window"]))
    n_seeds = int(cfg["seeds_per_call"])

    def dispatch(i):
        """Window call `i`, dispatched (JAX returns before the device
        has finished)."""
        return run_scenario_cell(
            policy, tr["scenario"], seeds=n_seeds, seed0=seed0(cell.seed, i),
            n_requests=int(cfg["n_requests"]), class_map=tr["class_map"],
            information=tr["information"], phys=phys, sim_cfg=sim_cfg)

    warm_call = 10**6  # a seed no window call uses
    jax.device_get(dispatch(warm_call))  # compiles; later calls find it
    cell.window_open()
    results = []
    t_start = time.perf_counter()
    t_end = t_start + cell.seconds
    now = t_start
    call_s = []
    dispatch_s = []
    while now < t_end:
        pending = dispatch(len(results))
        dispatch_s.append(time.perf_counter() - now)
        results.append(jax.device_get(pending))
        call_s.append(time.perf_counter() - now)
        now = time.perf_counter()
    wall = now - t_start
    cell.window_close()
    if cell.trace:
        # A whole call is some 6e6 device ops, past what the profiler can
        # hold, so the trace is a slice from inside one further call,
        # after the window: the call is dispatched, and the host sleeps
        # inside the annotation while the scan runs.
        pending = dispatch(len(results))
        time.sleep(float(tr["trace_offset_s"]))
        cell.trace_start()
        with cell.annotate("sim_call"):
            time.sleep(float(tr["trace_seconds"]))
        cell.trace_stop()
        jax.block_until_ready(pending)
        del pending
    cell.memory_peak()

    n_calls = len(results)
    ticks = n_calls * n_seeds * int(cfg["n_ticks"])
    n = int(cfg["n_requests"])
    prog = [outcomes(m, pm, n) for m, pm in results]
    abandoned = sum(int(o["abandoned"].sum()) for o in prog)
    # compare one call's seeds, drawn from the run's seed
    pick = int(np.random.default_rng([cell.seed, 7]).integers(n_calls))
    from bench.reference.scan_ref import run_reference

    s0 = seed0(cell.seed, pick)
    t0 = time.perf_counter()
    ref = run_reference(cfg, tr, list(range(s0, s0 + n_seeds)))
    check_s = time.perf_counter() - t0
    check = compare(prog[pick], ref)
    check.update(_calls=n_calls, _compared_call=pick, _seeds=n_seeds)
    return {
        "metrics": {"sim_ticks_per_s": ticks / wall},
        "attempted": n_calls * n_seeds * n, "failed": abandoned,
        "info": {"calls": n_calls, "window_s": wall,
                 "call_s": " ".join(f"{x:.4f}" for x in call_s),
                 "dispatch_s": " ".join(f"{x:.4f}" for x in dispatch_s),
                 "check_s": check_s},
        "check": check,
    }
