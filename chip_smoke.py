"""Bring-up smoke: the scheduler's main path, once, on one TPU chip.

Drives the entry points a user calls at the backlog and fleet sizes the
system is built for, in one process:

1. device  — JAX must see a TPU.  There is no CPU fallback.
2. kernel  — `sched_score_topb` through `kernels/sched_score/ops.py` at
             W in {1024, 4096} x B in {1, 16}, with and without the fleet
             route row, plus one exact-tie queue.  The lowered program
             must hold the compiled kernel (`tpu_custom_call`) and its
             indices must equal the `lax.top_k` oracle run on the chip.
3. live    — `ClientSession` over `MockProvider` (virtual clock, W=4096,
             B=16) drains a standing backlog of 1e5 requests on the
             "jnp" and the "pallas" ordering backend; the pallas tick must
             hold the compiled kernel.  Prints how many of the first 500
             polls' (actions, req_rids) differ between the two backends
             (recorded, not gated).
4. horizon — the 1e6-request `high_congestion` simulated horizon (the
             `scale_1e6` cell of `scenario_sweep.py --scale`): every
             metric finite, every request terminal.
5. fleet   — `fleet_failover` at P=16 at `fleet_sweep.py`'s own size:
             post-outage recovery >= its RECOVERY_BAR.

Each phase prints its compile seconds, wall seconds and counts on a line
of its own, labelled with the device.  The timings are one bring-up run,
not a benchmark.  Any failure raises, so the script exits non-zero and
prints no result; otherwise the last line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.

Usage, from the repository root (no PYTHONPATH needed):

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compilation_cache  # noqa: E402

SEED = 0
KERNEL_SHAPES = ((1024, 1), (1024, 16), (4096, 1), (4096, 16))
W4 = (1.0, 0.8, 0.5, 650.0)           # [w_wait, w_size, w_urg, ref_tokens]
W5 = W4 + (400.0,)                    # ... + w_route
LIVE_W, LIVE_B, LIVE_N = 4096, 16, 100_000
N_COMPARE = 500                       # polls compared across backends
HORIZON_TICKS, HORIZON_W = 14_000, 4096
FLEET_P = 16

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations, and
    counts backend compiles, across the whole process."""

    def __init__(self):
        self.seconds = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.n += event == _COMPILE_EVENTS[-1]


def run_phase(clock: CompileClock, label: str, name: str, fn, *args):
    """Run one phase and print its line; a failure propagates."""
    c0, n0 = clock.seconds, clock.n
    t0 = time.perf_counter()
    counts = fn(*args)
    wall = time.perf_counter() - t0
    extra = " ".join(f"{k}={v}" for k, v in counts.items())
    print(f"[{label}] {name}: compile_s={clock.seconds - c0:.3f} "
          f"compiles={clock.n - n0} wall_s={wall:.3f} {extra}", flush=True)
    return counts


def assert_compiled(lowered_text: str, what: str) -> None:
    """The Mosaic kernel is in the program: compiled, not interpreted."""
    if "tpu_custom_call" not in lowered_text:
        raise AssertionError(f"{what}: no compiled Pallas kernel "
                             f"(tpu_custom_call) in the program")


# --- phase 1 ---------------------------------------------------------------

def device_phase() -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); there is no CPU fallback")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# --- phase 2 ---------------------------------------------------------------

def _queue(rng, n: int, route: bool, tie: bool) -> list:
    wait = rng.uniform(0.0, 5e3, n).astype(np.float32)
    cost = (rng.uniform(0.0, 3000.0, n) + 0.5).astype(np.float32)
    urg = rng.uniform(0.0, 2.0, n).astype(np.float32)
    mask = rng.random(n) < 0.7
    if tie:  # the second half repeats the first: exact score ties
        half = n // 2
        for a in (wait, cost, urg):
            a[half:] = a[:half]
        mask[:] = True
    args = [wait, cost, urg, mask, np.asarray(W5 if route else W4, np.float32)]
    if route:
        args.append(rng.uniform(0.0, 3.0, n).astype(np.float32))
    return [jnp.asarray(a) for a in args]


def kernel_phase(shapes=KERNEL_SHAPES, tie_n: int = 4096) -> dict:
    from repro.kernels.sched_score import ops
    from repro.kernels.sched_score.ref import sched_score_topb_ref

    rng = np.random.default_rng(SEED)
    cases = [(n, b, route, False) for n, b in shapes for route in (False, True)]
    cases.append((tie_n, 16, False, True))
    score_diff = 0
    for n, b, route, tie in cases:
        args = _queue(rng, n, route, tie)
        lowered = jax.jit(
            lambda *a, b=b: ops.sched_score_topb(*a[:5], b, *a[5:])
        ).lower(*args)
        assert_compiled(lowered.as_text(),
                        f"sched_score_topb n={n} b={b} route={route}")
        idx, score = lowered.compile()(*args)
        ref_idx, ref_score = sched_score_topb_ref(*args[:5], b, *args[5:])
        idx, ref_idx = np.asarray(idx), np.asarray(ref_idx)
        if not np.array_equal(idx, ref_idx):
            raise AssertionError(
                f"sched_score_topb n={n} b={b} route={route} tie={tie}: "
                f"indices {idx.tolist()} != oracle {ref_idx.tolist()}")
        score_diff += int((np.asarray(score) != np.asarray(ref_score)).sum())
    return {"cases": len(cases), "indices_equal": len(cases),
            "score_ranks_not_bit_equal": score_diff}


# --- phase 3 ---------------------------------------------------------------

def live_phase(backend: str, logs: dict, n: int = LIVE_N, w: int = LIVE_W,
               b: int = LIVE_B) -> dict:
    from benchmarks.client_bench import _bench_policy, _fast_physics, _requests
    from repro.client import ClientSession, MockProvider, SessionConfig

    phys = _fast_physics()
    t0 = time.perf_counter()
    sess = ClientSession(
        MockProvider(phys, dt_ms=25.0), _bench_policy(),
        SessionConfig(window=w, max_grants=b, dt_ms=25.0, backend=backend),
        clock="virtual", phys=phys)
    setup = time.perf_counter() - t0
    for r in _requests(n):
        sess.submit(r)
    log = logs[backend] = []
    max_polls = 20 * (n // b + 50)
    t1 = time.perf_counter()
    while sess.unfinished:
        r = sess.poll()
        if len(log) < N_COMPARE:
            log.append((r.actions.copy(), r.req_rids.copy()))
        if sess.stats.n_polls > max_polls:
            raise AssertionError(f"live[{backend}]: {sess.unfinished} "
                                 f"unfinished after {max_polls} polls")
    drain = time.perf_counter() - t1
    done = sess.stats.n_completed
    if done != n:
        raise AssertionError(f"live[{backend}]: {done}/{n} completed")
    if backend == "pallas":
        assert_compiled(sess._tick.lower(
            sess._win_batch, sess._dev_state, None, sess._xfer).as_text(),
            "live[pallas] session tick")
    polls = sess.stats.n_polls
    return {"completed": f"{done}/{n}", "polls": polls,
            "setup_s": f"{setup:.3f}", "drain_s": f"{drain:.3f}",
            "poll_us": f"{drain / polls * 1e6:.1f}"}


def backend_disagreement(logs: dict) -> dict:
    a, b = logs["jnp"], logs["pallas"]
    m = min(len(a), len(b))
    differ = sum(
        not (np.array_equal(a[i][0], b[i][0])
             and np.array_equal(a[i][1], b[i][1])) for i in range(m))
    return {"polls_compared": m, "polls_differing": differ}


# --- phase 4 ---------------------------------------------------------------

def horizon_phase(n_ticks: int = HORIZON_TICKS, window: int = HORIZON_W,
                  n: int | None = None) -> dict:
    from benchmarks.scenario_sweep import REQUIRED_FINITE, SCALE_BASE_N, SCALE_N
    from repro.core.policy import strategy
    from repro.sim import SimConfig, run_scenario_cell

    n = SCALE_N if n is None else n
    m, pm = run_scenario_cell(
        strategy("final_adrr_olc"), "high_congestion", seeds=1,
        n_requests=n, arrival_scale=n / SCALE_BASE_N,
        sim_cfg=SimConfig(n_ticks=n_ticks, window=window))
    m, pm = jax.device_get((m, pm))
    metrics = {k: np.asarray(v, np.float64) for k, v in m._asdict().items()}
    # A percentile over an empty set is NaN by contract (`masked_percentile`):
    # under this overload no long request completes, on the CPU as well, so
    # `long_p90_ms` is NaN.  Only a percentile may be NaN; the sweep's own
    # aggregates and every other metric must be finite.
    empty = [k for k, v in metrics.items()
             if k.endswith(("_p90_ms", "_p95_ms")) and np.isnan(v).any()]
    bad = [k for k, v in metrics.items()
           if not np.isfinite(v).all() and k not in empty]
    bad += [k for k in REQUIRED_FINITE if k in empty]
    if bad:
        raise AssertionError(f"horizon: non-finite metrics {bad}")
    arrived = int(pm.n_arrived.sum())
    completed = int(pm.n_completed.sum())
    abandoned = int(pm.n_abandoned.sum())
    rejected = int(pm.shed_by_bucket.sum())
    terminal = completed + abandoned + rejected
    if arrived != n or terminal != n:
        raise AssertionError(f"horizon: {arrived} arrived, {terminal} "
                             f"terminal of {n}")
    return {"requests": n, "ticks": n_ticks, "window": window,
            "terminal": f"{terminal}/{n}", "completed": completed,
            "abandoned": abandoned, "rejected": rejected,
            "completion_rate": f"{float(m.completion_rate[0]):.4f}",
            "nan_empty_set": ",".join(empty) or "none"}


# --- phase 5 ---------------------------------------------------------------

def fleet_phase(p: int = FLEET_P, n: int = 160, n_ticks: int = 14_000,
                seeds: int = 3) -> dict:
    """`fleet_failover` at P endpoints, at `fleet_sweep.py`'s own size.

    The committed `fleet_sweep` rows were drawn before JAX 0.5 made the
    partitionable threefry stream the default, which changes every
    workload draw.  The recovery bar is applied to that committed
    workload (old stream), whose row the chip must also reproduce.  The
    installed default stream draws a seed whose post-outage arrivals are
    shed: its recovery is printed, gated only on finiteness."""
    from benchmarks.fleet_sweep import (
        BENCH_JSON, RECOVERY_BAR, REQUIRED_FINITE, _failover_at, _recovery,
    )
    from repro.core.policy import final_adrr_olc
    from repro.sim import SimConfig, run_scenario_cell, summarize, window_for

    scenario = _failover_at(p)

    def cell(partitionable: bool):
        with jax.threefry_partitionable(partitionable):
            m, pm = run_scenario_cell(
                final_adrr_olc(), scenario, seeds=seeds, n_requests=n,
                sim_cfg=SimConfig(n_ticks=n_ticks, window=window_for(n)))
        s = summarize(m)
        bad = [k for k in REQUIRED_FINITE if not np.isfinite(s[k][0])]
        if bad:
            raise AssertionError(f"fleet: non-finite metrics {bad}")
        return s, _recovery(pm)

    s, rec = cell(False)
    if not rec >= RECOVERY_BAR:
        raise AssertionError(f"fleet: recovery {rec:.4f} < {RECOVERY_BAR}")
    with open(BENCH_JSON) as f:
        row = next(c for c in json.load(f)["fleet_sweep"]["cells"]
                   if c["scenario"] == scenario.name)
    got = {k: round(s[k][0], 3) for k in
           ("completion_rate", "satisfaction", "n_rejects", "n_abandoned")}
    got["recovery"] = round(rec, 4)
    want = {k: row["aggregate"][k] for k in got if k != "recovery"}
    want["recovery"] = row["recovery"]
    if got != want:
        raise AssertionError(f"fleet: {got} != committed row {want}")
    _, rec_default = cell(True)
    return {"p": p, "requests": n, "seeds": seeds,
            "recovery": f"{rec:.4f}", "bar": RECOVERY_BAR,
            "committed_row": "reproduced",
            "completion_rate": f"{s['completion_rate'][0]:.4f}",
            "recovery_default_stream": f"{rec_default:.4f}"}


def main() -> int:
    enable_compilation_cache()
    clock = CompileClock()
    dev = device_phase()
    label = f"{dev['platform']} {dev['kind']} x{dev['count']}"
    run_phase(clock, label, "device", dict, dev)
    run_phase(clock, label, "kernel", kernel_phase)
    logs: dict = {}
    for backend in ("jnp", "pallas"):
        run_phase(clock, label, f"live[{backend}]", live_phase, backend, logs)
    run_phase(clock, label, "live[jnp vs pallas]", backend_disagreement, logs)
    run_phase(clock, label, "horizon", horizon_phase)
    run_phase(clock, label, "fleet", fleet_phase)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
