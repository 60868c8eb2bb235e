"""Tracing of the decision tick and of the live poll (repro.core.stages,
`ClientSession.enable_profiling`).

* Every stage of the tick names its ops with a `tick.<stage>` scope, in
  the compiled live tick and in the windowed scan program alike (the
  scopes sit in the stage functions both paths share).
* A profiled poll's phases are contiguous spans whose durations make up
  the `enable_profiling()` buckets, and profiling changes no decision.
* With profiling off a poll opens no profiler annotation at all.
"""
from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.client import ClientSession, MockProvider, Request, SessionConfig
from repro.client import session as session_mod
from repro.core import stages
from repro.core.policy import strategy
from repro.sim import SimConfig, WorkloadConfig, default_physics, generate
from repro.sim import runner
from repro.sim import scenarios as scn

SHARED = (stages.RETIRE, stages.ADMIT, stages.ORDER, stages.OVERLOAD,
          stages.GRANT, stages.APPLY)
STAGE_PHASES = ("ingest", "classify", "staging", "mirrors")


def _scopes(compiled_text: str) -> set[str]:
    return set(re.findall(r'op_name="[^"]*?(tick\.[a-z]+)',
                          compiled_text))


def _session(seed: int = 3, n: int = 48) -> ClientSession:
    """A session given a burst of `n` generated requests, all arrived at
    t = 0, so that the overload ladder defers some of them."""
    phys = default_physics()
    b, jitter = generate(jax.random.PRNGKey(seed), WorkloadConfig(
        n_requests=n, mix="balanced", congestion="high"))
    sess = ClientSession(
        MockProvider(phys, dt_ms=25.0), strategy("final_adrr_olc"),
        SessionConfig(window=32, max_grants=4, dt_ms=25.0),
        clock="virtual", phys=phys)
    cols = [np.asarray(x) for x in (b.bucket, b.cls, b.true_tokens, b.p50,
                                    b.p90, jitter)]
    for bucket, cls, tok, p50, p90, jit in zip(*cols):
        sess.submit(Request(rid=0, prompt=None, max_new=float(tok),
                            p50=float(p50), bucket=int(bucket),
                            p90=float(p90), cls=int(cls), arrival_s=0.0,
                            jitter=float(jit)))
    return sess


def test_live_tick_carries_every_shared_stage_scope():
    """The steady-state fused tick (with the previous epoch's decisions
    folded in) names every stage it runs."""
    sess = _session()
    sess.poll()
    assert sess._pending is not None
    compiled = sess._tick.lower(
        sess._win_batch, sess._dev_state, sess._pending, sess._comp,
        sess._staged_px, np.int32(0), np.float32(25.0)).compile()
    assert _scopes(compiled.as_text()) == set(SHARED)


@pytest.mark.parametrize("scenario,want", [
    ("high_congestion", set(SHARED)),
    ("fleet_skew", set(stages.STAGES)),  # P > 1: the routing stage too
])
def test_windowed_scan_carries_every_stage_scope(scenario, want):
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(0, 2))
    lowered = runner._run_scenario_seeds.lower(
        strategy("final_adrr_olc"), default_physics(), keys,
        scn.get_scenario(scenario),
        SimConfig(n_ticks=40, window=32, k_slots=4), 40, "paper2",
        "coarse", 1.0)
    assert _scopes(lowered.compile().as_text()) == want


class _Clock:
    """The session module's `time`, noting its last `perf_counter`
    reading, so that an annotation can record the reading at which the
    session entered or left it."""

    def __init__(self):
        self.last = None

    def perf_counter(self) -> float:
        self.last = time.perf_counter()
        return self.last

    def __getattr__(self, name):
        return getattr(time, name)


def test_stage_bucket_is_the_sum_of_its_phases(monkeypatch):
    """Each bucket is the sum of the intervals its phases' annotations
    cover on the session's clock: the phases follow one another without
    a gap in the documented order, and `stage` is ingest + classify +
    staging + mirrors."""
    clock, seen = _Clock(), []

    class Recording:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            seen.append((self.name, "enter", clock.last))
            return self

        def __exit__(self, *exc):
            seen.append((self.name, "exit", clock.last))
            return False

    monkeypatch.setattr(session_mod, "time", clock)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    sess = _session()
    prof = sess.enable_profiling()
    n = 120
    for _ in range(n):
        sess.poll()
    assert set(prof) == {"stage", "dispatch", "pull", "grants", "polls"}
    assert prof["polls"] == n
    names = ["session." + p for p in session_mod._PHASE_BUCKET]
    assert [x[0] for x in seen[::2]] == names * n
    assert all(a[1] == "enter" and b[1] == "exit" and a[0] == b[0]
               for a, b in zip(seen[::2], seen[1::2]))
    # within a poll each phase is entered at the reading its predecessor
    # was left at
    for k in range(n):
        poll = seen[14 * k:14 * (k + 1)]
        assert all(poll[i][2] == poll[i + 1][2] for i in range(1, 13, 2))
    covered = dict.fromkeys(STAGE_PHASES + ("dispatch", "pull", "grants"),
                            0.0)
    for (name, _, t0), (_, _, t1) in zip(seen[::2], seen[1::2]):
        assert t1 > t0
        covered[name[len("session."):]] += t1 - t0
    assert abs(prof["stage"] - sum(covered[p] for p in STAGE_PHASES)) \
        <= 1e-9 * n
    for bucket in ("dispatch", "pull", "grants"):
        assert abs(prof[bucket] - covered[bucket]) <= 1e-9 * n


def test_profiling_changes_no_decision():
    def run(profile: bool):
        sess = _session(seed=5)
        if profile:
            sess.enable_profiling()
        out = []
        for _ in range(300):
            r = sess.poll()
            out.append((r.actions.copy(), r.req_rids.copy(),
                        np.float32(r.severity).tobytes(), r.completed,
                        r.abandoned, r.rejected))
        return out, sess.stats

    (on, stats_on), (off, stats_off) = run(True), run(False)
    assert stats_on.n_admitted > 10 and stats_on.n_deferred > 0
    assert stats_on == stats_off
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        assert a[2:] == b[2:]


def test_profiling_off_opens_no_annotation(monkeypatch):
    opened = []

    class Counting:
        def __init__(self, name, **kwargs):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    sess = _session()
    for _ in range(20):
        sess.poll()
    assert opened == []
    sess.enable_profiling()
    sess.poll()
    assert opened == ["session." + p for p in session_mod._PHASE_BUCKET]
