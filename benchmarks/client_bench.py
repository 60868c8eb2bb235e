"""Streaming client-session throughput: the live-path half of the
windowed-scaling story.

`ClientSession` keeps O(W) state regardless of how many requests the
session has ever seen, so its per-poll cost — and therefore its
per-request rate at a fixed drain width — must be independent of the
total population N.  This driver measures end-to-end session throughput
(submit -> schedule_batch dispatch -> MockProvider -> completion) at
N ∈ {1e3, 1e5} over a fast-physics provider (service « dt, so the
scheduler, not the mock, is the bottleneck) and emits `client_session`
rows into BENCH_scheduler.json.  `benchmarks/check_regression.py` gates
both the absolute rates and the N-independence ratio (the N=1e5
per-request rate must stay within 2x of N=1e3).

`--smoke` is the CI serving smoke: a small session must drain to 100%
completion over the mock, and the deprecated ScheduledClient shim must
still run a closed list end to end.

The per-poll breakdown of the live path, on the chip and on the device
trace's clock, is the chip benchmark's traced run:
`python3 bench/run.py --workload frontend.high_congestion --seed <n>
--seconds <s> --trace 1`.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import merge_rows  # noqa: E402
from repro.client import (  # noqa: E402
    ClientSession,
    MockProvider,
    Request,
    SessionConfig,
)
from repro.core.policy import strategy  # noqa: E402
from repro.sim.provider import default_physics  # noqa: E402

N_SWEEP = (1_000, 100_000)
WINDOW = 1_024
GRANTS = 16
BENCH_JSON = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_scheduler.json")


def _bench_policy():
    """Throughput-shaped policy: overload control off (every grant
    admits), per-class and global concurrency caps lifted (the fast
    mock never congests, so caps would only meter the drain), and an
    effectively infinite timeout so a deep N=1e5 backlog measures
    dispatch throughput, not abandonment bookkeeping."""
    return strategy("adaptive_drr")._replace(
        timeout_mult=jnp.full((4,), 1e9, jnp.float32),
        class_cap=jnp.full((2,), 1e9, jnp.float32),
        max_inflight=jnp.float32(1e9))


def _fast_physics():
    """Service far below a tick: completions land next poll, so the
    session's own per-poll cost is the measured quantity."""
    return default_physics(base_ms=1.0, ms_per_token=0.0,
                           comfort_concurrency=1e9)


def _requests(n: int) -> list[Request]:
    # all arrived at t=0: worst-case standing queue, every poll admits
    # into a full window and dispatches a full grant batch
    return [
        Request(rid=i, prompt=None, max_new=8.0, p50=8.0,
                bucket=i % 4, arrival_s=0.0)
        for i in range(n)
    ]


def client_session_bench(n_requests: int, window: int = WINDOW,
                         grants: int = GRANTS, repeats: int = 3) -> dict:
    # Single-drain wall time swings ~1.5x run to run on a busy host, which
    # is wider than the check_regression tolerance band — report the best
    # of `repeats` full drains so both the committed rows and the in-gate
    # measurement see the machine's actual capability, not its worst
    # scheduling hiccup.
    policy = _bench_policy()
    phys = _fast_physics()
    best = None
    for _ in range(max(1, repeats)):
        sess = ClientSession(
            MockProvider(phys, dt_ms=25.0), policy,
            SessionConfig(window=window, max_grants=grants, dt_ms=25.0),
            clock="virtual", phys=phys)
        for r in _requests(n_requests):
            sess.submit(r)
        max_polls = 20 * (n_requests // grants + 50)
        t0 = time.perf_counter()
        sess.drain(max_polls=max_polls)
        wall = time.perf_counter() - t0
        n_done = sess.stats.n_completed
        if n_done != n_requests:
            raise RuntimeError(
                f"client_session_bench N={n_requests}: only {n_done} of "
                f"{n_requests} completed")
        row = {
            "n_requests": n_requests,
            "window": window,
            "max_grants": grants,
            "polls": sess.stats.n_polls,
            "poll_us": round(wall / sess.stats.n_polls * 1e6, 2),
            "requests_per_sec": round(n_requests / wall, 1),
        }
        if best is None or row["poll_us"] < best["poll_us"]:
            best = row
    return best


def write_client_bench(verbose: bool = True) -> str:
    prev = {}
    try:
        with open(BENCH_JSON) as f:
            prev = json.load(f)
    except (OSError, json.JSONDecodeError):
        pass
    rows = []
    for n in N_SWEEP:
        r = client_session_bench(n)
        rows.append(r)
        if verbose:
            print(f"  client_session N={n:7d} W={r['window']} "
                  f"B={r['max_grants']}: {r['poll_us']:8.1f}us/poll "
                  f"({r['requests_per_sec']:.0f} req/s)")
    prev["client_session"] = merge_rows(
        rows, prev.get("client_session", []),
        ("n_requests", "window", "max_grants"))
    by_n = {r["n_requests"]: r["requests_per_sec"] for r in rows}
    if len(N_SWEEP) == 2:
        ratio = by_n[N_SWEEP[1]] / by_n[N_SWEEP[0]]
        prev["client_session_n1e5_vs_n1e3_rate"] = round(ratio, 3)
        ok = ratio >= 0.5
        print(f"  [{'PASS' if ok else 'WARN'}] per-request rate at N=1e5 is "
              f"{ratio:.2f}x the N=1e3 rate "
              f"({'meets' if ok else 'MISSES'} the windowed "
              f"N-independence bar of >=0.5x)")
    with open(BENCH_JSON, "w") as f:
        json.dump(prev, f, indent=2)
    return BENCH_JSON


def smoke() -> int:
    """CI serving smoke: session over MockProvider drains to 100%, and
    the deprecated ScheduledClient shim still serves a closed list."""
    policy = _bench_policy()
    phys = _fast_physics()
    sess = ClientSession(
        MockProvider(phys, dt_ms=25.0), policy,
        SessionConfig(window=64, max_grants=8, dt_ms=25.0),
        clock="virtual", phys=phys)
    n = 256
    for r in _requests(n):
        sess.submit(r)
    sess.drain(max_polls=5000)
    if sess.stats.n_completed != n:
        print(f"FAIL: serving smoke completed {sess.stats.n_completed}/{n}")
        return 1
    print(f"  serving smoke: ClientSession drained {n}/{n} "
          f"in {sess.stats.n_polls} polls")

    import warnings

    from repro.serving import ScheduledClient

    class _Echo:
        def submit(self, prompt, max_new):
            return np.arange(int(max_new), dtype=np.int32)

    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32), max_new=4.0,
                    p50=4.0, bucket=0) for i in range(4)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        out = ScheduledClient(_Echo(), strategy("final_adrr_olc")).run(
            reqs, time_scale=40.0)
    bad = [r.rid for r in out if r.status != "completed"]
    if bad:
        print(f"FAIL: serving smoke shim left {bad} uncompleted")
        return 1
    print("  serving smoke: ScheduledClient shim completed 4/4")
    return 0


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        sys.exit(smoke())
    write_client_bench()
