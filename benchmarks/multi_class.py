"""Beyond-paper: config-driven K-class scheduling sweep.

Exercises the tentpole generalization — the same three-layer stack
instantiated at K ∈ {2, 4, 8} tenants under balanced/heavy congestion —
and reports:

  * per-class joint metrics (P95 / deadline satisfaction / goodput) so
    multi-tenant fairness is legible per lane, plus the cross-class
    dispersion that the DRR allocation is supposed to bound;
  * scheduler-step wall-clock per K (the vectorized class axis must be
    no slower at K=2 than the seed two-lane path, and ~flat in K);
  * batch-dispatch throughput: `schedule_batch` at B ∈ {1, 4, 16}
    grants per tick × queue depth N ∈ {1e3, 1e5} — the multi-grant pass
    amortizes the O(K·N) layer-2 work over B grants, so slots/sec must
    scale super-linearly vs B sequential single-slot traces (the
    acceptance bar is ≥2× at B=16 vs B=1 at equal tick budgets);
  * active-window dispatch throughput (DESIGN.md §6): the windowed
    per-tick policy path at N ∈ {1e3, 1e5[, 1e6 with --scale]} × W ∈
    {1024, 4096} plus end-to-end windowed engine ticks/sec — per-tick
    cost is O(W), so the rate must be ~flat in N where the dense rows
    collapse ~30× (the acceptance bar is ≥10× the dense B=1 N=1e5
    rate), and the N=1e6 engine row is the population the dense scan
    cannot run at all;
  * a `BENCH_scheduler.json` microbenchmark artifact (all sweeps) so
    future PRs have a perf trajectory to compare against.

The K=2 cell runs the paper's `paper2` lane scheme with the seed policy
(bit-exact with the seed scheduler — tests/test_multi_class.py), so its
per-class metrics double as the seed-equivalence check: lane 0 equals
the short-bucket scalars within seed noise.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.sim.engine as eng  # noqa: E402
from repro.core.policy import base_policy, kclass_policy, n_classes  # noqa: E402
from repro.core.scheduler import schedule_batch, schedule_slot  # noqa: E402
from repro.core.types import (  # noqa: E402
    WindowCarry,
    init_sim_state,
)
from repro.sim import (  # noqa: E402
    SimConfig,
    WorkloadConfig,
    default_physics,
    run_cell,
    run_sim,
    summarize,
)

from benchmarks.common import (  # noqa: E402
    Timer,
    merge_rows,
    write_csv,
)

K_SWEEP = (2, 4, 8)
B_SWEEP = (1, 4, 16)           # grants per batched dispatch pass
N_SWEEP = (1_000, 100_000)     # queue depths (requests resident)
# active-window sweep (DESIGN.md §6): horizon population x window
# capacity.  N_SCALE only runs under --scale (`make bench-scale`) —
# the dense path cannot touch it at all, the windowed rows prove it
# runs; rows for skipped Ns are preserved from the committed artifact.
W_SWEEP = (1_024, 4_096)
N_SWEEP_WIN = (1_000, 100_000)
N_SCALE = 1_000_000
WB_SWEEP = (1, 16)             # grants per windowed dispatch pass
REGIMES = [("balanced", "medium"), ("heavy", "high")]
MAX_K = max(K_SWEEP)
BENCH_JSON = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_scheduler.json")


def _policy_for(k: int):
    """K=2 runs the seed (paper) policy on the paper2 lanes; K>2 runs the
    symmetric-tenant instantiation of the same stack."""
    return base_policy() if k == 2 else kclass_policy(k)


def _workload_for(k: int, mix: str, congestion: str, n_req: int):
    cmap = "paper2" if k == 2 else f"tenant{k}"
    return WorkloadConfig(
        n_requests=n_req, mix=mix, congestion=congestion, class_map=cmap)


def _cell_row(k, mix, congestion, s, secs):
    row = {
        "n_classes": k,
        "mix": mix,
        "congestion": congestion,
        "cell_seconds": round(secs, 2),
    }
    for key in ("global_p95_ms", "completion_rate", "satisfaction",
                "goodput_rps", "n_rejects"):
        row[f"{key}_mean"] = round(s[key][0], 3)
    for c in range(MAX_K):
        for key in ("class_p95_ms", "class_satisfaction", "class_goodput_rps"):
            v = s.get(f"{key}#{c}")
            row[f"{key.replace('class_', '')}_c{c}"] = (
                round(v, 3) if v is not None else "")
    return row


def _per_class_summary(m, k):
    """mean over seeds for each class lane, flattened to scalar keys."""
    out = summarize(m)
    flat = {kk: vv for kk, vv in out.items()}
    for name in ("class_p95_ms", "class_satisfaction", "class_goodput_rps"):
        arr = np.asarray(getattr(m, name), np.float64)  # (seeds, K)
        for c in range(k):
            col = arr[:, c]
            finite = col[np.isfinite(col)]
            # a lane can be empty in short smoke runs: report NaN quietly
            flat[f"{name}#{c}"] = (
                float(finite.mean()) if finite.size else float("nan"))
    return flat


def scheduler_step_bench(k: int, n_req: int = 256, iters: int = 300) -> dict:
    """Wall-clock of one jitted schedule_slot at class count K."""
    policy = _policy_for(k)
    wl = _workload_for(k, "heavy", "high", n_req)
    from repro.sim.workload import generate

    batch, _ = generate(jax.random.PRNGKey(0), wl)
    state = init_sim_state(batch.n, n_classes(policy))._replace(
        now_ms=jnp.float32(1e5))
    step = jax.jit(schedule_slot)

    t0 = time.perf_counter()
    d = step(policy, batch, state)
    jax.block_until_ready(d)
    compile_s = time.perf_counter() - t0

    # best-of-3: shared-container noise easily swamps a single block
    run_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            d = step(policy, batch, state)
        jax.block_until_ready(d)
        run_s = min(run_s, time.perf_counter() - t0)
    return {
        "n_classes": k,
        "n_requests": n_req,
        "compile_seconds": round(compile_s, 4),
        "slot_us": round(run_s / iters * 1e6, 2),
        "slots_per_sec": round(iters / run_s, 1),
    }


def batch_dispatch_bench(b: int, n_req: int, iters: int = 100) -> dict:
    """Wall-clock of one jitted schedule_batch granting up to B per call
    at queue depth N.  slots/sec counts grant opportunities (B × calls),
    the apples-to-apples rate against B sequential schedule_slot calls
    at an equal tick budget."""
    policy = base_policy()
    wl = _workload_for(2, "heavy", "high", n_req)
    from repro.sim.workload import generate

    batch, _ = generate(jax.random.PRNGKey(0), wl)
    state = init_sim_state(batch.n, n_classes(policy))._replace(
        now_ms=jnp.float32(1e7))  # everything arrived: worst-case queue
    step = jax.jit(schedule_batch, static_argnames=("max_grants", "backend"))

    t0 = time.perf_counter()
    d = step(policy, batch, state, max_grants=b)
    jax.block_until_ready(d)
    compile_s = time.perf_counter() - t0

    run_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            d = step(policy, batch, state, max_grants=b)
        jax.block_until_ready(d)
        run_s = min(run_s, time.perf_counter() - t0)
    return {
        "max_grants": b,
        "n_requests": n_req,
        "compile_seconds": round(compile_s, 4),
        "call_us": round(run_s / iters * 1e6, 2),
        "slots_per_sec": round(b * iters / run_s, 1),
    }


def _full_window(n_req: int, w: int):
    """Worst-case live queue: a full window of arrived pending work over
    an N-deep horizon population.  Slot i holds request i (the window is
    request-id sorted by construction, matching the engine invariant)."""
    policy = base_policy()
    wl = _workload_for(2, "heavy", "high", n_req)
    from repro.sim.workload import generate

    batch, jitter = generate(jax.random.PRNGKey(0), wl)
    state = init_sim_state(batch.n, n_classes(policy))._replace(
        now_ms=jnp.float32(1e7))
    win = WindowCarry(
        slot_req=jnp.arange(w, dtype=jnp.int32),
        arr_ptr=jnp.int32(w),
        n_live=jnp.int32(w),
    )
    return policy, batch, jitter, state, win


def windowed_dispatch_bench(b: int, n_req: int, w: int,
                            iters: int = 100) -> dict:
    """Wall-clock of one windowed dispatch step — the active-window
    engine's per-tick policy path: gather the (W,) window view, run
    `schedule_batch` over (K, W), translate slot decisions to global
    request ids.  Cost is O(W) by construction; `n_req` only sets the
    population the view gathers from, so the rate should be ~flat in N
    at fixed W — the tentpole property the dense rows above collapse on.
    """
    assert w <= n_req
    policy, batch, _, state, win = _full_window(n_req, w)
    table = eng.pack_batch(batch)  # once, outside the step, as run_sim does

    @functools.partial(jax.jit, static_argnames=("max_grants",))
    def step(state, win, max_grants):
        wb, wr, _ = eng._window_view(batch, state.req, win.slot_req, table)
        d = schedule_batch(policy, wb, state._replace(req=wr),
                           max_grants=max_grants)
        return d._replace(req_idx=win.slot_req[jnp.clip(d.req_idx, 0, w - 1)])

    t0 = time.perf_counter()
    d = step(state, win, max_grants=b)
    jax.block_until_ready(d)
    compile_s = time.perf_counter() - t0

    run_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            d = step(state, win, max_grants=b)
        jax.block_until_ready(d)
        run_s = min(run_s, time.perf_counter() - t0)
    return {
        "max_grants": b,
        "n_requests": n_req,
        "window": w,
        "compile_seconds": round(compile_s, 4),
        "call_us": round(run_s / iters * 1e6, 2),
        "slots_per_sec": round(b * iters / run_s, 1),
    }


def windowed_engine_bench(n_req: int, w: int, n_ticks: int = 400,
                          k_slots: int = 16) -> dict:
    """End-to-end windowed `run_sim` throughput (ticks/sec) at horizon
    population N — admission, compaction, retirement scatters and the
    dispatch pass included.  The N=1e6 row is the feasibility proof: the
    dense engine's per-tick O(K*N) scan cannot run that population at
    all (extrapolated ~3 slots/s from the committed N=1e5 collapse)."""
    policy = base_policy()
    wl = _workload_for(2, "heavy", "high", n_req)
    from repro.sim.workload import generate

    batch, jitter = generate(jax.random.PRNGKey(0), wl)
    phys = default_physics()
    cfg = SimConfig(n_ticks=n_ticks, k_slots=k_slots, window=w)

    run = jax.jit(lambda: run_sim(policy, batch, jitter, phys, cfg))
    t0 = time.perf_counter()
    jax.block_until_ready(run())
    compile_and_first_s = time.perf_counter() - t0

    run_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        run_s = min(run_s, time.perf_counter() - t0)
    return {
        "n_requests": n_req,
        "window": w,
        "n_ticks": n_ticks,
        "k_slots": k_slots,
        "first_run_seconds": round(compile_and_first_s, 3),
        "ticks_per_sec": round(n_ticks / run_s, 1),
        "grant_opps_per_sec": round(k_slots * n_ticks / run_s, 1),
    }


def write_windowed_bench(bench: dict, prev: dict, scale: bool = False,
                         verbose: bool = True) -> None:
    """Active-window N x W sweep appended into the BENCH artifact."""
    n_sweep = N_SWEEP_WIN + ((N_SCALE,) if scale else ())
    rows = []
    for n_req in n_sweep:
        # a window cannot exceed the population; small-N cells fall back
        # to W=N (the window covers everything — the dense-equivalent)
        ws = [w for w in W_SWEEP if w <= n_req] or [n_req]
        for w in ws:
            for b in WB_SWEEP:
                r = windowed_dispatch_bench(b, n_req, w, iters=100)
                rows.append(r)
                if verbose:
                    print(f"  windowed    B={b:2d} N={n_req:7d} W={w:5d}: "
                          f"{r['call_us']:9.1f}us/call "
                          f"({r['slots_per_sec']:.0f} slots/s)")
    bench["windowed_dispatch"] = merge_rows(
        rows, prev.get("windowed_dispatch", []),
        ("max_grants", "n_requests", "window"))

    erows = []
    for n_req in n_sweep:
        er = windowed_engine_bench(n_req, w=min(4096, n_req))
        erows.append(er)
        if verbose:
            print(f"  engine(win) N={n_req:7d} W={er['window']:5d}: "
                  f"{er['ticks_per_sec']:.0f} ticks/s "
                  f"({er['grant_opps_per_sec']:.0f} grant-opps/s)")
    bench["windowed_engine"] = merge_rows(
        erows, prev.get("windowed_engine", []), ("n_requests",))

    # headline ratios: windowed vs dense dispatch at the deep queue —
    # the tentpole acceptance bar is >=10x the dense B=1 N=1e5 rate at
    # a production-sized window (per-W keys: the W=1024 cell is the
    # live-queue-sized operating point, W=4096 the worst case)
    dense = {(r["max_grants"], r["n_requests"]): r["slots_per_sec"]
             for r in bench.get("batch_dispatch", [])}
    win = {(r["max_grants"], r["n_requests"], r["window"]): r["slots_per_sec"]
           for r in bench["windowed_dispatch"]}
    base = dense.get((1, 100_000))
    best = 0.0
    for w in W_SWEEP:
        fresh = win.get((1, 100_000, w))
        if base and fresh:
            ratio = fresh / base
            best = max(best, ratio)
            bench[f"win_vs_dense_b1_rate_n100000_w{w}"] = round(ratio, 3)
    if best:
        ok = best >= 10.0
        print(f"  [{'PASS' if ok else 'WARN'}] windowed B=1 N=1e5 dispatch "
              f"up to {best:.1f}x the dense rate "
              f"({'meets' if ok else 'MISSES'} the >=10x bar)")


def write_batch_bench(bench: dict, verbose: bool = True) -> None:
    """B × N batch-dispatch sweep appended into the BENCH artifact."""
    rows = []
    for n_req in N_SWEEP:
        iters = 100 if n_req <= 10_000 else 20
        base_rate = None
        for b in B_SWEEP:
            r = batch_dispatch_bench(b, n_req, iters=iters)
            rows.append(r)
            if b == 1:
                base_rate = r["slots_per_sec"]
            if verbose:
                print(f"  schedule_batch B={b:2d} N={n_req:6d}: "
                      f"{r['call_us']:9.1f}us/call "
                      f"({r['slots_per_sec']:.0f} slots/s)")
        ratio = rows[-1]["slots_per_sec"] / base_rate
        key = f"b16_vs_b1_rate_ratio_n{n_req}"
        bench[key] = round(ratio, 3)
        ok = ratio >= 2.0
        print(f"  [{'PASS' if ok else 'WARN'}] N={n_req}: B=16 grants "
              f"{ratio:.1f}x the B=1 slot rate at equal tick budgets "
              f"({'meets' if ok else 'MISSES'} the >=2x bar)")
    bench["batch_dispatch"] = rows


# aggregate summary keys that must be finite in every cell (exactly the
# columns _cell_row emits): NaN/inf here means a degenerate run (nothing
# arrived or completed), which must fail loudly — a silent pass would
# blind the CI bench gate.  Per-lane values are exempt: a lane can be
# legitimately empty in short smoke runs.
REQUIRED_FINITE = (
    "global_p95_ms", "completion_rate", "satisfaction", "goodput_rps",
    "n_rejects",
)


def check_finite(rows: list[dict]) -> list[str]:
    """Returns violation strings for any non-finite required aggregate."""
    bad = []
    for row in rows:
        for key in REQUIRED_FINITE:
            v = row.get(f"{key}_mean")
            if v is None or not np.isfinite(v):
                bad.append(
                    f"K={row['n_classes']} {row['mix']}/{row['congestion']}: "
                    f"{key}_mean = {v}")
    return bad


def run(verbose: bool = True, n_ticks: int | None = None, n_req: int = 160,
        seeds: int = 5, sched_bench: bool = True):
    sim_cfg = SimConfig(n_ticks=n_ticks if n_ticks is not None else 14000)
    rows = []
    k2_summary = {}
    for mix, congestion in REGIMES:
        for k in K_SWEEP:
            wl = _workload_for(k, mix, congestion, n_req)
            with Timer() as t:
                m = run_cell(_policy_for(k), wl, seeds=seeds, sim_cfg=sim_cfg)
                jax.block_until_ready(m.class_p95_ms)
            s = _per_class_summary(m, k)
            if k == 2:
                k2_summary[(mix, congestion)] = s
            rows.append(_cell_row(k, mix, congestion, s, t.s))
            if verbose:
                lanes = " ".join(
                    f"c{c}:{s[f'class_satisfaction#{c}']:.2f}"
                    for c in range(k))
                print(f"  K={k} {mix}/{congestion:6s} {t.s:5.1f}s "
                      f"goodput={s['goodput_rps'][0]:.2f} sat/lane [{lanes}]")

    path = write_csv("multi_class_summary", rows)

    # --- seed-equivalence readout: paper2 lane 0 == short-bucket scalars
    for (mix, congestion), s in k2_summary.items():
        short_scalar = s["short_p95_ms"][0]
        lane0 = s["class_p95_ms#0"]
        ok = (not np.isfinite(short_scalar)) or abs(lane0 - short_scalar) <= max(
            0.05 * short_scalar, 1.0)
        print(f"  [{'PASS' if ok else 'WARN'}] K=2 {mix}/{congestion}: lane-0 "
              f"P95 {lane0:.0f}ms matches short-bucket scalar "
              f"{short_scalar:.0f}ms")

    violations = check_finite(rows)
    if violations:
        # raise (don't just return) so every driver — __main__/--smoke,
        # benchmarks/run.py, an interactive call — fails loudly
        print("FAIL: non-finite aggregate metrics:")
        for v in violations:
            print(f"  {v}")
        raise RuntimeError(
            f"degenerate benchmark run: {len(violations)} non-finite "
            f"aggregate metric(s)")

    # --- scheduler-step microbenchmark -> BENCH_scheduler.json
    # (skipped in smoke: the committed artifact is the full run's, and
    # the CI regression gate compares fresh numbers against it)
    if sched_bench:
        write_sched_bench(verbose=verbose)
    return path, BENCH_JSON


def write_sched_bench(verbose: bool = True, iters: int = 300,
                      scale: bool = False) -> str:
    """Scheduler-throughput microbenchmark: slots/sec per K, the
    batch-dispatch B × N sweep, and the active-window N × W sweep,
    written to BENCH_scheduler.json so future PRs have a perf
    trajectory.  `scale` adds the N=1e6 cells (`make bench-scale`);
    without it the committed N=1e6 rows are carried forward."""
    prev = {}
    try:
        with open(BENCH_JSON) as f:
            prev = json.load(f)
    except (OSError, json.JSONDecodeError):
        pass
    bench = {"benchmark": "schedule_slot", "steps": []}
    base_rate = None
    for k in K_SWEEP:
        b = scheduler_step_bench(k, iters=iters)
        bench["steps"].append(b)
        if k == 2:
            base_rate = b["slots_per_sec"]
        if verbose:
            print(f"  schedule_slot K={k}: {b['slot_us']:7.1f}us/slot "
                  f"({b['slots_per_sec']:.0f} slots/s, "
                  f"compile {b['compile_seconds']:.2f}s)")
    k8_rate = bench["steps"][-1]["slots_per_sec"]
    bench["k8_vs_k2_rate_ratio"] = round(k8_rate / base_rate, 3)
    ok = k8_rate >= 0.5 * base_rate
    print(f"  [{'PASS' if ok else 'WARN'}] K=8 scheduler rate "
          f"{'within' if ok else 'NOT within'} 2x of K=2 "
          f"(vectorized class axis)")
    # persist the K sweep before the (longer) batch sweep, then rewrite
    # with the batch rows — an interrupted B x N run can't lose the data
    # already computed
    with open(BENCH_JSON, "w") as f:
        json.dump(bench, f, indent=2)
    write_batch_bench(bench, verbose=verbose)
    with open(BENCH_JSON, "w") as f:
        json.dump(bench, f, indent=2)
    write_windowed_bench(bench, prev, scale=scale, verbose=verbose)
    with open(BENCH_JSON, "w") as f:
        json.dump(bench, f, indent=2)
    return BENCH_JSON


if __name__ == "__main__":
    if "--sched-only" in sys.argv:
        write_sched_bench(scale="--scale" in sys.argv)
    else:
        smoke = "--smoke" in sys.argv
        try:
            run(n_ticks=300 if smoke else None,
                n_req=48 if smoke else 160,
                seeds=2 if smoke else 5,
                sched_bench=not smoke)
        except RuntimeError as e:
            print(e)
            sys.exit(1)
