"""Entry for the live front end: `ClientSession.poll` over `MockProvider`.

Set-up builds the session from the configuration file (the session's
own warm-up compiles its (W, B, K) tick) and runs the traffic's warm
polls.  Before every poll, set-up's and the window's alike, the requests
of the open-loop stream (bench/generator.py) that arrive by the poll's
time are submitted with their own arrival times; the window polls until
`--seconds` have passed.  A traced run then polls on for the traffic's
`trace_seconds` under the profiler, so that the trace's cost stays out
of the window and of the session's profiling buckets.  Every poll's
grant rows, severity and retirements are logged; once the window has
closed the plain reference replays the same stream over the logged rows
(bench/reference).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.generator import RequestStream, arrival_ms
from bench.reference.session_ref import IDLE, SessionReference, policy_table


def _policy(config: dict):
    import jax.numpy as jnp
    from repro.core.policy import PolicyConfig

    table = policy_table(config)
    return PolicyConfig(**{k: jnp.asarray(table[k]) for k in PolicyConfig._fields})


def _requests(feats: dict) -> list:
    from repro.client import Request

    return [Request(rid=0, prompt=None, max_new=float(t), p50=float(p50),
                    bucket=int(b), p90=float(p90), arrival_s=float(a),
                    jitter=float(j))
            for b, t, p50, p90, j, a in zip(
                feats["bucket"], feats["tokens"], feats["p50"], feats["p90"],
                feats["jitter"], feats["arrival_s"])]


def poll_time_ms(t: int, dt_ms: float) -> np.float32:
    """The session's clock at its `t`-th virtual poll (1-based)."""
    return np.float32(np.float32(t) * np.float32(dt_ms))


def run(cell) -> dict:
    from repro.client import ClientSession, MockProvider, SessionConfig
    from repro.sim.provider import default_physics

    cfg, tr = cell.config, cell.traffic
    dt_ms = float(cfg["dt_ms"])
    phys = default_physics(**cfg["provider"])
    sess = ClientSession(
        MockProvider(phys, dt_ms=dt_ms), _policy(cfg),
        SessionConfig(window=int(cfg["window"]),
                      max_grants=int(cfg["max_grants"]), dt_ms=dt_ms,
                      backend=cfg["backend"]),
        clock=cfg["clock"], phys=phys)
    stream = RequestStream(tr, cfg["provider"], cell.seed)
    log: list = []

    def arrive() -> None:
        """Submit the requests that arrive by the next poll's time."""
        for r in _requests(stream.take_until(poll_time_ms(len(log) + 1, dt_ms))):
            sess.submit(r)

    def poll() -> None:
        r = sess.poll()
        log.append((r.actions.copy(), r.req_rids.copy(), r.severity,
                    r.completed, r.abandoned, r.rejected))

    for _ in range(int(tr["warm_polls"])):
        arrive()
        poll()
    n_warm = len(log)
    prof = sess.enable_profiling() if cell.trace else None

    cell.window_open()
    poll_s: list[float] = []
    t_start = time.perf_counter()
    t_end = t_start + cell.seconds
    now = t_start
    while now < t_end:
        arrive()
        t0 = time.perf_counter()
        poll()
        now = time.perf_counter()
        poll_s.append(now - t0)
    wall = now - t_start
    cell.window_close()
    n_window = len(log)
    profile = dict(prof) if prof is not None else None
    if cell.trace:
        # the device trace: a slice of further polls after the window, so
        # that the profiler's cost stays out of the window and its buckets
        cell.trace_start()
        t_stop = time.perf_counter() + float(tr["trace_seconds"])
        while time.perf_counter() < t_stop:
            with cell.annotate("arrive"):
                arrive()
            with cell.annotate("poll"):
                poll()
        cell.trace_stop()

    window = log[n_warm:n_window]
    decisions = sum(int((a != IDLE).sum()) for a, *_ in window)
    abandoned = sum(len(x[4]) for x in window)
    out = {
        "metrics": {"polls_per_s": len(poll_s) / wall,
                    "poll_p99_ms": float(np.percentile(poll_s, 99)) * 1e3},
        "attempted": decisions, "failed": abandoned,
        "info": {"polls": len(poll_s), "warm_polls": n_warm,
                 "decisions": decisions,
                 "poll_p50_ms": float(np.percentile(poll_s, 50)) * 1e3,
                 "poll_max_ms": max(poll_s) * 1e3,
                 "window_s": wall},
        "profile": profile,
    }
    cell.memory_peak()
    del sess
    gc.collect()
    t0 = time.perf_counter()
    out["check"] = check(cfg, tr, cell.seed, log)
    out["info"]["check_s"] = time.perf_counter() - t0
    return out


def replay(config: dict, traffic: dict, seed: int, n_polls: int, ref,
           rows_of) -> SessionReference:
    """Drive `ref` through `n_polls` epochs of the cell's traffic, checking
    at poll i the (rows, severity, retired) that `rows_of(i)` gives."""
    stream = RequestStream(traffic, config["provider"], seed)
    for i in range(n_polls):
        feats = stream.take_until(poll_time_ms(i + 1, float(config["dt_ms"])))
        if len(feats["bucket"]):
            ref.submit(feats, arrival_ms(feats["arrival_s"]))
        ref.poll(*rows_of(i))
    return ref


def check(config: dict, traffic: dict, seed: int, log: list) -> dict:
    """Compare every logged poll with the plain reference."""
    ref = replay(config, traffic, seed, len(log), SessionReference(config),
                 lambda i: ((log[i][0], log[i][1]), log[i][2], log[i][3:]))
    return readings_dict(ref.readings)


def readings_dict(rd) -> dict:
    """The compared numbers (`decision_gap`: the wider of the pick and
    the class-choice gap), then the readings behind them (`_`-named)."""
    return {"decision_gap": max(rd.pick_gap, rd.alloc_gap),
            "severity_gap": rd.severity_gap, "mismatches": rd.mismatches,
            "_pick_gap": rd.pick_gap, "_alloc_gap": rd.alloc_gap,
            "_polls": rd.polls, "_decisions": rd.decisions,
            "_first_mismatch": rd.first_mismatch}
