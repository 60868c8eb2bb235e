"""First-ever serving-path coverage: the streaming `ClientSession`
(DESIGN.md §7).

The headline pin: driven in virtual time over `MockProvider`,
`ClientSession` reproduces the windowed sim engine's decision sequence
— same action, same target request, tick for tick, grant for grant —
on generated traces (the `balanced` regime plus a nonstationary one).
The session and engine share `schedule_batch`, `_complete_and_timeout`,
and the provider physics, so this is the sim↔live parity contract made
executable.  Severity is compared to 1 ulp rather than bitwise: the
EMA's trailing multiply-add contracts to an FMA inside the engine's
scan fusion but not in the session's standalone programs, a 1-ulp
rounding difference LLVM applies below the reach of
`core.numerics.pinned` (decisions pinned here are robust to it).

Also covered: the 429/Retry-After boundary under a rate_crunch-style
throttle schedule (bounces honored, no resubmission before the window,
recovery after it lifts, the retry-policy hook), drain lifecycle,
open-ended submission, p90 defaulting, and the deprecated
`ScheduledClient` shim.
"""
from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from repro.client import (
    AsyncBlackBoxProvider,
    ClientSession,
    MockProvider,
    Request,
    SessionConfig,
    default_p90,
    expo_retry,
)
from repro.core.policy import strategy
from repro.core.scheduler import IDLE
from repro.sim import SimConfig, WorkloadConfig, default_physics, generate, run_sim
from repro.sim import scenarios as scn
from repro.sim.workload import P90_OVER_P50_NP


def batch_to_requests(batch, jitter) -> list[Request]:
    """Replay a generated RequestBatch as session submissions (arrival
    order == request-id order, the generator's native sort)."""
    arr = np.asarray(batch.arrival_ms)
    bucket = np.asarray(batch.bucket)
    cls = np.asarray(batch.cls)
    tok = np.asarray(batch.true_tokens)
    p50 = np.asarray(batch.p50)
    p90 = np.asarray(batch.p90)
    jit = np.asarray(jitter)
    return [
        Request(
            rid=i, prompt=None, max_new=float(tok[i]), p50=float(p50[i]),
            bucket=int(bucket[i]), p90=float(p90[i]), cls=int(cls[i]),
            arrival_s=float(arr[i]) / 1e3, jitter=float(jit[i]),
        )
        for i in range(batch.n)
    ]


def drive_session(sess: ClientSession, n_ticks: int):
    """n_ticks virtual polls; returns (actions (T,B), rids (T,B),
    severity (T,))."""
    acts, rids, sevs = [], [], []
    for _ in range(n_ticks):
        r = sess.poll()
        acts.append(r.actions)
        rids.append(r.req_rids)
        sevs.append(r.severity)
    return np.stack(acts), np.stack(rids), np.asarray(sevs, np.float32)


def assert_decision_parity(trace, s_acts, s_rids, s_sevs):
    e_acts = np.asarray(trace[0])
    e_idxs = np.asarray(trace[1])
    e_sevs = np.asarray(trace[2], np.float32)
    np.testing.assert_array_equal(s_acts, e_acts)
    live = e_acts != IDLE
    np.testing.assert_array_equal(s_rids[live], e_idxs[live])
    # 1 ulp on severity (see module docstring); decisions above are exact
    np.testing.assert_allclose(s_sevs, e_sevs, rtol=3e-7, atol=1e-9)


class TestDecisionParity:
    """Acceptance pin: ClientSession over MockProvider == the windowed
    sim engine's decision stream."""

    def _pair(self, wl, seed, n_ticks, window, k_slots, policy_name):
        policy = strategy(policy_name)
        batch, jitter = generate(jax.random.PRNGKey(seed), wl)
        phys = default_physics()
        sim_cfg = SimConfig(n_ticks=n_ticks, k_slots=k_slots, dt_ms=25.0,
                            window=window)
        _, trace = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg,
            collect_decisions=True))()
        sess = ClientSession(
            MockProvider(phys, dt_ms=25.0), policy,
            SessionConfig(window=window, max_grants=k_slots, dt_ms=25.0),
            clock="virtual", phys=phys)
        for r in batch_to_requests(batch, jitter):
            sess.submit(r)
        return trace, sess

    def test_balanced_pinned(self):
        wl = WorkloadConfig(n_requests=48, mix="balanced",
                            congestion="medium")
        trace, sess = self._pair(wl, seed=0, n_ticks=900, window=64,
                                 k_slots=4, policy_name="final_adrr_olc")
        s_acts, s_rids, s_sevs = drive_session(sess, 900)
        assert_decision_parity(trace, s_acts, s_rids, s_sevs)
        # the pin must bite: real admits and completions happened
        assert sess.stats.n_admitted > 10
        assert sess.stats.n_completed > 10

    def test_balanced_seed1(self):
        wl = WorkloadConfig(n_requests=48, mix="balanced",
                            congestion="medium")
        trace, sess = self._pair(wl, seed=1, n_ticks=900, window=64,
                                 k_slots=4, policy_name="final_adrr_olc")
        assert_decision_parity(trace, *drive_session(sess, 900))

    @pytest.mark.slow
    def test_heavy_high_overload_path(self):
        """Overload regime (arrivals compressed 3x): defers/rejects flow
        through the same parity — the cost ladder fires, not just
        admits."""
        wl = WorkloadConfig(n_requests=96, mix="heavy", congestion="high",
                            arrival_scale=3.0)
        trace, sess = self._pair(wl, seed=2, n_ticks=1200, window=128,
                                 k_slots=4, policy_name="final_adrr_olc")
        s_acts, s_rids, s_sevs = drive_session(sess, 1200)
        assert_decision_parity(trace, s_acts, s_rids, s_sevs)
        assert sess.stats.n_rejected + sess.stats.n_deferred > 0

    @pytest.mark.slow
    def test_flash_crowd_nonstationary(self):
        """Nonstationary arrivals (no provider dynamics): the time-warped
        trace replays identically through the live path."""
        sc = scn.get_scenario("flash_crowd")
        sim_cfg = SimConfig(n_ticks=1200, k_slots=4, dt_ms=25.0, window=128)
        wl, sched, dyn, _ = scn.build(sc, 96, sim_cfg.n_ticks, sim_cfg.dt_ms)
        assert dyn is None
        policy = strategy("final_adrr_olc")
        batch, jitter = generate(jax.random.PRNGKey(3), wl, sched)
        phys = default_physics()
        _, trace = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg,
            collect_decisions=True))()
        sess = ClientSession(
            MockProvider(phys, dt_ms=25.0), policy,
            SessionConfig(window=128, max_grants=4, dt_ms=25.0),
            clock="virtual", phys=phys)
        for r in batch_to_requests(batch, jitter):
            sess.submit(r)
        assert_decision_parity(trace, *drive_session(sess, 1200))


class TestThrottleBackoff:
    """The 429/Retry-After boundary under a rate_crunch-style schedule:
    sustained refill collapses mid-run, the bucket drains, bounces carry
    Retry-After, and the session parks bounced work for exactly that
    long."""

    def _crunch_provider(self, phys, n_ticks=2000, dt=25.0,
                         retry_after=1500.0):
        t = np.arange(n_ticks)
        # 1.2 grants/s sustained, frozen to 10% for the middle third
        refill = np.full((n_ticks, 2), 1.2 * dt / 1000.0, np.float32)
        mid = (t >= n_ticks // 3) & (t < 2 * n_ticks // 3)
        refill[mid] *= 0.1
        return MockProvider(
            phys, dt_ms=dt, tb_refill=refill,
            tb_capacity=np.full(2, 4.0, np.float32),
            retry_after_ms=retry_after)

    def _arrival_burst(self, n, gap_ms=120.0):
        return [
            Request(rid=i, prompt=None, max_new=40.0 + i, p50=40.0 + i,
                    bucket=0, arrival_s=i * gap_ms / 1e3)
            for i in range(n)
        ]

    @staticmethod
    def _patient_policy():
        """The crunch outlasts the shorts' stale timeout; relax the
        timeout multiple so the test isolates Retry-After behavior and
        post-crunch recovery from client-side abandonment."""
        import jax.numpy as jnp
        return strategy("final_adrr_olc")._replace(
            timeout_mult=jnp.full((4,), 30.0, jnp.float32))

    def test_throttles_happen_and_backoff_is_honored(self):
        phys = default_physics()
        prov = self._crunch_provider(phys)
        sess = ClientSession(
            prov, self._patient_policy(),
            SessionConfig(window=64, max_grants=4, dt_ms=25.0),
            clock="virtual", phys=phys)
        for r in self._arrival_burst(40):
            sess.submit(r)
        throttle_at: dict[int, float] = {}   # rid -> bounce time
        resubmit_gap_ok = True
        for _ in range(2400):
            r = sess.poll()
            for rid in r.throttled:
                throttle_at[rid] = r.now_ms
            for rid in r.admitted:
                if rid in throttle_at:
                    # bounced earlier: must not resubmit before Retry-After
                    if r.now_ms < throttle_at[rid] + prov.retry_after_ms:
                        resubmit_gap_ok = False
            if sess.unfinished == 0:
                break
        assert prov.n_throttled > 0, "crunch never produced a 429"
        assert sess.stats.n_throttled == prov.n_throttled
        assert resubmit_gap_ok, "a bounced request resubmitted early"
        # recovery: after the window lifts everything completes
        assert sess.unfinished == 0
        assert sess.stats.n_completed == 40
        # the session's per-request bookkeeping saw the bounces too
        assert sum(r.n_throttles for r in sess.requests()) \
            == prov.n_throttled

    def test_retry_policy_hook(self):
        """expo_retry grows the park time geometrically per bounce of
        the same request — the pluggable Retry-After policy.  The
        default ±20% jitter smears each delay, so the spacing bound is
        the jittered floor 0.8 * growth^(i-1) * retry_after."""
        phys = default_physics()
        prov = self._crunch_provider(phys, retry_after=400.0)
        sess = ClientSession(
            prov, self._patient_policy(),
            SessionConfig(window=64, max_grants=4, dt_ms=25.0),
            clock="virtual", phys=phys,
            retry_policy=expo_retry(mult=1.0, growth=3.0))
        for r in self._arrival_burst(40, gap_ms=80.0):
            sess.submit(r)
        bounces: dict[int, list[float]] = {}
        for _ in range(3000):
            r = sess.poll()
            for rid in r.throttled:
                bounces.setdefault(rid, []).append(r.now_ms)
            if sess.unfinished == 0:
                break
        multi = {rid: ts for rid, ts in bounces.items() if len(ts) >= 2}
        assert prov.n_throttled > 0
        assert multi, "no request bounced twice — the hook went unexercised"
        # the delay applied after the i-th bounce of a request is at
        # least 0.8 * retry_after * growth^(i-1); the gap to its next
        # bounce must respect it
        for rid, ts in multi.items():
            for i in range(1, len(ts)):
                grown = 400.0 * 3.0 ** (i - 1)
                assert ts[i] - ts[i - 1] >= 0.8 * min(grown, 60_000.0) - 1e-3

    def test_expo_retry_jitter_distribution(self):
        """The jitter decorrelates a synchronized 429 cohort: delays for
        the same (retry_after, n_throttles) spread uniformly over
        base * [1 - j, 1 + j] instead of collapsing to one value, and
        replays are deterministic under the same seed."""
        policy = expo_retry(mult=1.0, growth=2.0, jitter=0.2, seed=7)
        base = 400.0 * 2.0 ** 2  # third bounce
        draws = np.asarray([policy(400.0, 3) for _ in range(400)])
        assert draws.min() >= 0.8 * base - 1e-9
        assert draws.max() <= 1.2 * base + 1e-9
        # genuinely spread (a lockstep cohort would be a point mass) and
        # roughly uniform: both halves of the band are populated
        assert np.unique(draws).size > 390
        assert draws.std() > 0.08 * base
        lo_half = (draws < base).mean()
        assert 0.35 < lo_half < 0.65
        # seeded determinism: an identical policy replays identically
        replay = expo_retry(mult=1.0, growth=2.0, jitter=0.2, seed=7)
        assert [replay(400.0, 3) for _ in range(400)] == list(draws)
        # jitter=0 recovers the exact geometric schedule (and the cap)
        exact = expo_retry(mult=1.0, growth=3.0, jitter=0.0)
        assert exact(400.0, 1) == 400.0
        assert exact(400.0, 3) == 3600.0
        assert exact(400.0, 20) == 60_000.0


class TestSessionLifecycle:
    def test_open_ended_submission(self):
        """Requests submitted mid-flight (after polling started) are
        admitted and completed — the API is a stream, not a batch."""
        phys = default_physics()
        sess = ClientSession(
            MockProvider(phys, dt_ms=25.0), strategy("final_adrr_olc"),
            SessionConfig(window=16, max_grants=2, dt_ms=25.0),
            clock="virtual", phys=phys)
        sess.submit(Request(rid=0, prompt=None, max_new=30.0, p50=30.0,
                            bucket=0))
        for _ in range(40):
            sess.poll()
        late = Request(rid=1, prompt=None, max_new=30.0, p50=30.0, bucket=0,
                       arrival_s=sess.now_ms() / 1e3)
        sess.submit(late)
        out = sess.drain(max_polls=4000)
        assert [r.status for r in out] == ["completed", "completed"]
        assert out[1].finish_s > out[0].finish_s

    def test_window_overflow_queues_fifo(self):
        """More live work than W: the queue holds the overflow and every
        request still terminates (the engine's overflow contract)."""
        phys = default_physics()
        sess = ClientSession(
            MockProvider(phys, dt_ms=25.0), strategy("final_adrr_olc"),
            SessionConfig(window=4, max_grants=2, dt_ms=25.0),
            clock="virtual", phys=phys)
        for i in range(16):
            sess.submit(Request(rid=i, prompt=None, max_new=25.0, p50=25.0,
                                bucket=0))
        out = sess.drain(max_polls=8000)
        assert all(r.status in ("completed", "rejected", "abandoned")
                   for r in out)
        assert sum(r.status == "completed" for r in out) > 0
        assert sess._n_live <= 4

    def test_inflight_tracks_provider_concurrency(self):
        """The session's concurrency accounting equals the provider's
        actual outstanding count every epoch (no blocking brackets)."""
        phys = default_physics()
        prov = MockProvider(phys, dt_ms=25.0)
        sess = ClientSession(
            prov, strategy("final_adrr_olc"),
            SessionConfig(window=32, max_grants=4, dt_ms=25.0),
            clock="virtual", phys=phys)
        for i in range(24):
            sess.submit(Request(rid=i, prompt=None, max_new=200.0,
                                p50=200.0, bucket=1))
        saw_concurrent = False
        for _ in range(1500):
            sess.poll()
            sess_inflight = int(np.asarray(sess._state.provider.inflight))
            assert sess_inflight == prov.inflight()
            saw_concurrent |= prov.inflight() > 1
            if sess.unfinished == 0:
                break
        assert saw_concurrent, "never had >1 request in flight"

    def test_p90_defaulting(self):
        r = Request(rid=0, prompt=None, max_new=100.0, p50=100.0, bucket=2)
        assert r.resolved_p90() == pytest.approx(
            100.0 * float(P90_OVER_P50_NP[2]))
        assert default_p90(1.0, 0) == pytest.approx((64.0 / 16.0) ** 0.4)
        explicit = Request(rid=0, prompt=None, max_new=100.0, p50=100.0,
                           bucket=2, p90=555.0)
        assert explicit.resolved_p90() == 555.0


class TestDonationSafety:
    """The fused tick's perf contract: the (W,) pool is donated (the
    device reuses the buffers in place, the host never rematerializes
    them), and a drained session's polls are host-only no-ops."""

    def _session(self, window=16):
        phys = default_physics()
        return ClientSession(
            MockProvider(phys, dt_ms=25.0), strategy("final_adrr_olc"),
            SessionConfig(window=window, max_grants=2, dt_ms=25.0),
            clock="virtual", phys=phys)

    def test_fused_tick_donates_pool_buffers(self):
        """Every (W,)-sized device buffer of the pre-poll (batch, state)
        pool must be consumed by the fused step — a silently dropped
        donation would double the pool's memory and re-copy it every
        poll.  (A handful of scalar/(K,) fields legitimately escape:
        the deferred-apply decision in `_pending` keeps aliases of
        deficit/rr_turn/inflight alive across the epoch boundary, so
        XLA declines those donations — bytes, not the O(W) pool.)"""
        sess = self._session()
        sess.submit(Request(rid=0, prompt=None, max_new=25.0, p50=25.0,
                            bucket=0))
        sess.poll()  # fold the warmup-fresh pool through one real epoch
        w = sess.cfg.window
        before = [x for x in jax.tree_util.tree_leaves(
            (sess._win_batch, sess._dev_state)) if x.size >= w]
        assert len(before) >= 14  # the pool really is (W,)-columnar
        sess.poll()
        assert all(x.is_deleted() for x in before)

    def test_stale_post_donation_read_raises(self):
        """The invariant reprolint RPL002 enforces statically, verified
        dynamically: a binding captured before a poll is donated into
        the fused tick, and a host read of the stale Array must raise
        (deleted buffer) rather than silently observe freed memory.
        `poll()` itself stays safe because it rebinds `_win_batch` /
        `_dev_state` from the tick's results in the same statement."""
        sess = self._session()
        sess.submit(Request(rid=0, prompt=None, max_new=25.0, p50=25.0,
                            bucket=0))
        sess.poll()  # fold the warmup-fresh pool through one real epoch
        w = sess.cfg.window
        stale = [x for x in jax.tree_util.tree_leaves(
            (sess._win_batch, sess._dev_state)) if x.size >= w]
        assert stale, "expected (W,)-sized donated leaves"
        sess.poll()  # donates every captured buffer
        for leaf in stale:
            assert leaf.is_deleted()
            with pytest.raises(RuntimeError):
                np.asarray(leaf)  # any host materialization must fail

    def test_transfer_buffers_untouched_until_pull(self):
        """The tick is dispatched asynchronously and may read its NumPy
        staging arguments in place (zero-copy on the CPU) until it ends.
        The host must not refill them between the dispatch and the
        blocking summary pull: doing so raced the device and made
        decisions vary from run to run."""
        sess = self._session()
        for i in range(6):
            sess.submit(Request(rid=i, prompt=None, max_new=25.0, p50=25.0,
                                bucket=0))
        tick, checks = sess._tick, []

        class _Summary:  # checks the staged args when the host pulls
            def __init__(self, summary, staged):
                self.summary, self.staged = summary, staged

            def __array__(self, dtype=None, copy=None):
                checks.append(all(np.array_equal(buf, snap)
                                  for buf, snap in self.staged))
                return np.asarray(self.summary, dtype)

        def spy(*args):
            staged = [(a, a.copy()) for a in args if isinstance(a, np.ndarray)]
            *out, summary = tick(*args)
            return (*out, _Summary(summary, staged))

        sess._tick = spy
        out = sess.drain(max_polls=4000)
        assert all(r.status == "completed" for r in out)
        assert checks and all(checks)

    def test_post_drain_poll_is_transfer_free(self):
        """After drain() the pool is empty and the epoch is a fixpoint:
        poll() must replay the cached result without touching the
        device at all — no transfers in either direction."""
        sess = self._session()
        for i in range(4):
            sess.submit(Request(rid=i, prompt=None, max_new=25.0, p50=25.0,
                                bucket=0))
        sess.drain(max_polls=4000)
        assert sess._idle_cache is not None
        with jax.transfer_guard("disallow"):
            r1 = sess.poll()
            r2 = sess.poll()
        assert not r1.progressed and not r2.progressed
        assert r1.n_live == 0
        assert r2.now_ms > r1.now_ms  # the clock still advances

    def test_submit_after_drain_invalidates_idle_cache(self):
        """A new submission must break the fixpoint: the next poll goes
        back through the device and the request completes."""
        sess = self._session()
        sess.submit(Request(rid=0, prompt=None, max_new=25.0, p50=25.0,
                            bucket=0))
        sess.drain(max_polls=4000)
        assert sess._idle_cache is not None
        sess.submit(Request(rid=1, prompt=None, max_new=25.0, p50=25.0,
                            bucket=0, arrival_s=sess.now_ms() / 1e3))
        assert sess._idle_cache is None
        out = sess.drain(max_polls=4000)
        assert out[1].status == "completed"


class _EchoProvider:
    """Blocking stand-in for the real engine (submit(prompt, max_new))."""

    def submit(self, prompt, max_new):
        time.sleep(0.002)
        return np.arange(int(max_new), dtype=np.int32)


class TestWallClockAndShim:
    def test_async_blackbox_adapter(self):
        """Wall-clock session over the threaded adapter: non-blocking
        submits, multiple inflight, outputs delivered."""
        prov = AsyncBlackBoxProvider(_EchoProvider(), max_workers=4)
        phys = default_physics()
        sess = ClientSession(
            prov, strategy("final_adrr_olc"),
            SessionConfig(window=16, max_grants=4, time_scale=50.0),
            clock="wall", phys=phys)
        for i in range(6):
            sess.submit(Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                                max_new=5.0, p50=5.0, bucket=0))
        out = sess.drain()
        prov.shutdown()
        assert all(r.status == "completed" for r in out)
        assert all(r.output is not None and len(r.output) == 5 for r in out)

    def test_adapter_max_inflight_throttles(self):
        """The adapter's concurrency cap emits real 429s the session
        backs off from — Retry-After at the real-engine boundary."""
        prov = AsyncBlackBoxProvider(_EchoProvider(), max_workers=2,
                                     max_inflight=1, retry_after_ms=50.0)
        sess = ClientSession(
            prov, strategy("final_adrr_olc"),
            SessionConfig(window=16, max_grants=4, time_scale=50.0),
            clock="wall", phys=default_physics())
        for i in range(8):
            sess.submit(Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                                max_new=4.0, p50=4.0, bucket=0))
        out = sess.drain()
        prov.shutdown()
        assert all(r.status == "completed" for r in out)
        assert prov.n_throttled > 0

    def test_scheduled_client_shim(self):
        """The deprecated closed-list surface still runs end to end over
        the new session (and warns)."""
        reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                        max_new=4.0 + i, p50=4.0 + i, bucket=0,
                        arrival_s=0.02 * i) for i in range(5)]
        from repro.serving import ScheduledClient
        with pytest.warns(DeprecationWarning):
            client = ScheduledClient(_EchoProvider(),
                                     strategy("final_adrr_olc"))
        out = client.run(reqs, time_scale=40.0)
        assert all(r.status == "completed" for r in out)
        assert all(r.output is not None for r in out)
