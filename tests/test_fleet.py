"""Fleet dispatch pins (DESIGN.md §10).

Contracts under test:

- **P=1 transparency** — a single-endpoint fleet is bit-exact with
  today's single-provider engine: same decision stream, same request
  arrays, same service-time bit patterns.  The fleet axis must be a
  pure generalization, not a parallel implementation.
- **Dense/windowed parity at P>1** — routing, the per-endpoint
  limiter, and the failover requeue all ride the windowed engine's
  bit-exact contract.
- **Failover** — killing an endpoint mid-run requeues its in-flight
  work (visible in `FleetState.n_requeued` and per-request throttle
  counts) and the run still drains every request to a terminal state.
- **Skew** — routing sends more traffic to faster endpoints.
- **FleetProvider** — the live-path adapter routes, drains down
  endpoints gracefully, merges completions in ticket order, and
  passes through transparently at P=1.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import base_policy, n_classes, strategy
from repro.core.routing import UNAVAIL_MS, route_requests
from repro.core.types import COMPLETED, init_fleet_state
from repro.sim import (
    Fleet,
    FleetDynamics,
    ProviderDynamics,
    SimConfig,
    WorkloadConfig,
    default_physics,
    generate,
    run_sim,
    uniform_fleet_physics,
)
from repro.sim import scenarios as scn

REQ_FIELDS = ("status", "submit_ms", "finish_ms", "defer_until",
              "n_defers", "n_throttles")


def _bits_equal(a, b):
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _mk_fleet(p, speed_mult=None, comfort_mult=None, avail=None,
              tb_refill=None, tb_capacity=None, retry_after_ms=1500.0):
    fphys = uniform_fleet_physics(default_physics(), p,
                                  speed_mult=speed_mult,
                                  comfort_mult=comfort_mult)
    dyn = FleetDynamics(avail=avail, comfort_scale=None,
                        tb_refill=tb_refill, tb_capacity=tb_capacity,
                        retry_after_ms=jnp.float32(retry_after_ms))
    return Fleet(phys=fphys, dyn=dyn)


def _assert_same_run(a, b, *, compare_endpoint=False):
    """Request arrays, scheduler floats, and decision stream bit-equal
    between two (final, trace) run_sim results."""
    (fa, ta), (fb, tb) = a, b
    for name in REQ_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(fa.req, name)),
            np.asarray(getattr(fb.req, name)), err_msg=name)
    if compare_endpoint:
        np.testing.assert_array_equal(
            np.asarray(fa.req.endpoint), np.asarray(fb.req.endpoint))
    assert _bits_equal(fa.sched.ema_latency_ratio, fb.sched.ema_latency_ratio)
    assert _bits_equal(fa.sched.deficit, fb.sched.deficit)
    assert int(fa.sched.rr_turn) == int(fb.sched.rr_turn)
    a_act, b_act = np.asarray(ta[0]), np.asarray(tb[0])
    np.testing.assert_array_equal(a_act, b_act)
    from repro.core.scheduler import IDLE
    a_idx = np.where(a_act == IDLE, -1, np.asarray(ta[1]))
    b_idx = np.where(b_act == IDLE, -1, np.asarray(tb[1]))
    np.testing.assert_array_equal(a_idx, b_idx)
    assert _bits_equal(ta[2], tb[2])


class TestP1Transparency:
    """fleet=Fleet(P=1) must compile to the single-provider program."""

    @pytest.mark.parametrize("limiter", ["off", "on"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_bit_exact_with_plain_engine(self, seed, limiter):
        """With the limiter on, the plain engine's (K,) bucket and the
        P=1 fleet's (1, K) grid must bounce the same grants, through
        the same verdict transition."""
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=120, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(seed), wl)
        phys = default_physics()
        sim_cfg = SimConfig(n_ticks=2000, k_slots=4)
        dyn, fl = None, _mk_fleet(1)
        if limiter == "on":
            k = n_classes(policy)
            refill = jnp.full((sim_cfg.n_ticks, k), 0.02, jnp.float32)
            cap = jnp.full((k,), 2.0, jnp.float32)
            dyn = ProviderDynamics(comfort_scale=None, tb_refill=refill,
                                   tb_capacity=cap,
                                   retry_after_ms=jnp.float32(1500.0))
            fl = _mk_fleet(1, tb_refill=refill[:, None, :],
                           tb_capacity=cap[None, :])
        plain = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg, dyn,
            collect_decisions=True))()
        fleet = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg,
            fleet=fl, collect_decisions=True))()
        _assert_same_run(plain, fleet)
        n_thr = int(plain[0].provider.n_throttled)
        assert int(fleet[0].provider.n_throttled) == n_thr
        assert int(np.asarray(fleet[0].fleet.n_throttled).sum()) == n_thr
        if limiter == "on":
            assert n_thr >= 1, "the limiter never bounced a grant"
        # service times bit-identical: the P==1 gather must reproduce
        # the exact scalar physics program, not a re-rounded variant
        assert _bits_equal(plain[0].req.finish_ms, fleet[0].req.finish_ms)
        assert int((np.asarray(plain[0].req.status) == COMPLETED).sum()) > 10
        # the fleet run carries its bookkeeping without disturbance
        assert fleet[0].fleet is not None
        assert int(np.asarray(fleet[0].fleet.n_requeued).sum()) == 0

    def test_bit_exact_windowed(self):
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=96, mix="balanced",
                            congestion="medium")
        batch, jitter = generate(jax.random.PRNGKey(2), wl)
        phys = default_physics()
        sim_cfg = SimConfig(n_ticks=2000, k_slots=4, window=128)
        plain = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg,
            collect_decisions=True))()
        fleet = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg,
            fleet=_mk_fleet(1), collect_decisions=True))()
        _assert_same_run(plain, fleet)


class TestFleetEngineParity:
    """Dense vs windowed at P>1: the fleet layers ride the bit-exact
    window contract."""

    def _run_pair(self, policy, batch, jitter, sim_cfg, window, fleet):
        phys = default_physics()
        dense = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg, fleet=fleet,
            collect_decisions=True))()
        win = jax.jit(lambda: run_sim(
            policy, batch, jitter, phys, sim_cfg._replace(window=window),
            fleet=fleet, collect_decisions=True))()
        return dense, win

    def test_p4_uniform(self, fleet_batch=None):
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=120, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(3), wl)
        pair = self._run_pair(policy, batch, jitter,
                              SimConfig(n_ticks=2000, k_slots=4),
                              window=160, fleet=_mk_fleet(4))
        _assert_same_run(*pair, compare_endpoint=True)
        d, w = pair[0][0].fleet, pair[1][0].fleet
        np.testing.assert_array_equal(np.asarray(d.inflight),
                                      np.asarray(w.inflight))
        np.testing.assert_array_equal(np.asarray(d.n_requeued),
                                      np.asarray(w.n_requeued))

    def test_p4_failover_requeues_and_recovers(self):
        """Endpoint 0 dies for ticks [400, 1200): its in-flight work is
        requeued (PENDING + Retry-After defer + throttle bump), both
        engines agree, and the horizon still completes everything."""
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=120, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(4), wl)
        T = 6000
        avail = jnp.ones((T, 4), jnp.float32).at[400:1200, 0].set(0.0)
        fleet = _mk_fleet(4, avail=avail)
        pair = self._run_pair(policy, batch, jitter,
                              SimConfig(n_ticks=T, k_slots=4),
                              window=160, fleet=fleet)
        _assert_same_run(*pair, compare_endpoint=True)
        final = pair[0][0]
        requeued = np.asarray(final.fleet.n_requeued)
        assert requeued.sum() > 0          # the failover actually bit
        assert requeued[1:].sum() == 0     # only the dead endpoint
        np.testing.assert_array_equal(
            requeued, np.asarray(pair[1][0].fleet.n_requeued))
        # requeued work carries the throttle bump; every request still
        # reaches a terminal state (heavy/high legitimately abandons a
        # tail — the outage must not strand anyone mid-flight)
        from repro.core.types import ABANDONED, REJECTED
        st = np.asarray(final.req.status)
        assert ((st == COMPLETED) | (st == REJECTED)
                | (st == ABANDONED)).all()
        assert int((st == COMPLETED).sum()) > 90
        assert int(np.asarray(final.req.n_throttles).sum()) >= requeued.sum()

    def test_p4_per_endpoint_token_bucket(self):
        """A starved bucket on every endpoint throttles grants
        per-(endpoint, class); counts agree dense vs windowed."""
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=120, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(5), wl)
        # starvation math: ~15 requests land per (endpoint, class) bucket
        # but refill only grants ~4 tokens over the horizon, so some
        # admits must bounce off the limiter
        T, P, K = 4000, 4, 2
        refill = jnp.full((T, P, K), 0.001, jnp.float32)
        cap = jnp.full((P, K), 1.0, jnp.float32)
        fleet = _mk_fleet(4, tb_refill=refill, tb_capacity=cap)
        pair = self._run_pair(policy, batch, jitter,
                              SimConfig(n_ticks=T, k_slots=4),
                              window=160, fleet=fleet)
        _assert_same_run(*pair, compare_endpoint=True)
        thr = np.asarray(pair[0][0].fleet.n_throttled)
        assert thr.sum() > 0
        np.testing.assert_array_equal(
            thr, np.asarray(pair[1][0].fleet.n_throttled))


class TestRoutingBehavior:
    def test_skew_prefers_fast_endpoints(self):
        """speed_mult (0.5, 1, 1, 2): the cheapest-cost endpoint takes
        the most completions, the 2x-slow one the least."""
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=160, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(6), wl)
        fleet = _mk_fleet(4, speed_mult=(0.5, 1.0, 1.0, 2.0))
        final = jax.jit(lambda: run_sim(
            policy, batch, jitter, default_physics(),
            SimConfig(n_ticks=3000, k_slots=4), fleet=fleet))()
        ep = np.asarray(final.req.endpoint)
        done = np.asarray(final.req.status) == COMPLETED
        counts = np.bincount(ep[done], minlength=4)
        assert counts.sum() > 50
        assert counts[0] > counts[3]

    def test_route_requests_unit(self):
        """The routing layer in isolation: load balance, failover
        masking, and P=1 degeneracy."""
        fphys = uniform_fleet_physics(default_physics(), 3)
        fs = init_fleet_state(3, 2)._replace(
            inflight=jnp.asarray([8, 0, 0], jnp.int32))
        p50 = jnp.full((5,), 200.0, jnp.float32)
        ep, route = route_requests(fphys, fs, p50)
        assert np.asarray(ep).shape == (5,) and np.asarray(route).shape == (5,)
        # loaded endpoint 0 loses to the idle ones; ties break low
        np.testing.assert_array_equal(np.asarray(ep), np.ones(5) * 1)
        assert (np.asarray(route) > 0).all()
        assert (np.asarray(route) < UNAVAIL_MS * 1e-3).all()
        # endpoint 1 down -> 2 wins (0 is congested)
        ep2, _ = route_requests(
            fphys, fs, p50, avail_t=jnp.asarray([1.0, 0.0, 1.0]))
        np.testing.assert_array_equal(np.asarray(ep2), np.ones(5) * 2)

    def test_fleet_scenarios_registered(self):
        for name in ("fleet_failover", "fleet_skew", "fleet_brownout"):
            sc = scn.get_scenario(name)
            assert sc.fleet is not None and sc.fleet.p == 4
            fleet = scn.build_fleet(sc, default_physics(), 3000, 25.0,
                                    120, 2)
            assert fleet.phys.base_ms.shape == (4,)

    def test_fleet_scenario_end_to_end(self):
        """Registry fleet scenario through the seed-vmapped runner."""
        from repro.sim import run_scenario_cell
        m, pm = run_scenario_cell(
            base_policy(), "fleet_skew", seeds=2, n_requests=96,
            sim_cfg=SimConfig(n_ticks=2000, k_slots=4))
        assert float(np.nanmean(np.asarray(m.completion_rate))) > 0.3


class TestFleetProviderLive:
    def _children(self, fphys_np, **kw):
        from repro.client import MockProvider
        from repro.sim.provider import ProviderPhysics
        return [MockProvider(ProviderPhysics(
            *(float(np.asarray(a)[i]) for a in fphys_np)), **kw)
            for i in range(np.asarray(fphys_np.base_ms).shape[0])]

    def _mk(self, p=4, speed_mult=None, avail=None):
        from repro.client import FleetProvider
        fphys = uniform_fleet_physics(default_physics(), p,
                                      speed_mult=speed_mult)
        fphys_np = type(fphys)(*(np.asarray(a) for a in fphys))
        return FleetProvider(self._children(fphys_np), fphys_np,
                             avail=avail)

    def _req(self, i, p50=100.0):
        from repro.client import Request
        return Request(rid=i, prompt=None, max_new=p50, p50=p50, bucket=1)

    def test_routing_balances_and_skews(self):
        fp = self._mk(4, speed_mult=(0.5, 1.0, 1.0, 2.0))
        for i in range(16):
            assert fp.submit(self._req(i), now_ms=50.0).accepted
        by_ep = fp.inflight_by_endpoint()
        assert fp.inflight() == 16
        assert by_ep[0] > by_ep[3]      # fast endpoint loads first
        assert (by_ep > 0).sum() >= 2   # comfort pressure spreads load

    def test_poll_merges_in_ticket_order(self):
        fp = self._mk(4)
        for i in range(10):
            assert fp.submit(self._req(i), now_ms=50.0).accepted
        comps = fp.poll(1e9)
        assert [c.ticket for c in comps] == sorted(c.ticket for c in comps)
        assert len(comps) == 10 and fp.inflight() == 0

    def test_down_endpoint_drains_gracefully(self):
        """An endpoint that goes down stops receiving but still
        completes what it holds — the live-path failure model."""
        avail = np.ones((400, 2), np.float32)
        avail[4:, 0] = 0.0  # endpoint 0 dies after ~100ms
        fp = self._mk(2, avail=avail)
        r = fp.submit(self._req(0), now_ms=50.0)
        assert r.accepted and fp.n_routed[0] == 1
        for i in range(1, 7):
            assert fp.submit(self._req(i), now_ms=500.0).accepted
        assert fp.n_routed[0] == 1      # nothing new landed on the corpse
        assert fp.inflight_by_endpoint()[0] == 1
        comps = fp.poll(1e9)            # ...but its work still drains
        assert len(comps) == 7

    def test_whole_fleet_down_bounces_with_retry_after(self):
        avail = np.zeros((10, 2), np.float32)
        fp = self._mk(2, avail=avail)
        res = fp.submit(self._req(0), now_ms=50.0)
        assert not res.accepted and res.retry_after_ms == 1500.0
        assert fp.n_refused == 1

    def test_p1_passthrough_matches_bare_child(self):
        """P=1 fleet forwards inflight_hint and prices service exactly
        like the bare MockProvider."""
        from repro.client import MockProvider
        phys = default_physics()
        bare = MockProvider(phys)
        fp = self._mk(1)
        for i in range(6):
            rb = bare.submit(self._req(i), now_ms=50.0, inflight_hint=i)
            rf = fp.submit(self._req(i), now_ms=50.0, inflight_hint=i)
            assert rb.accepted and rf.accepted
        cb = bare.poll(1e9)
        cf = fp.poll(1e9)
        np.testing.assert_array_equal(
            np.asarray([c.finish_ms for c in cb], np.float32),
            np.asarray([c.finish_ms for c in cf], np.float32))

    def test_from_fleet_scenario(self):
        from repro.client import FleetProvider
        sc = scn.get_scenario("fleet_failover")
        fp = FleetProvider.from_fleet_scenario(
            sc, n_requests=120, n_ticks=6000, dt_ms=25.0, k=4)
        assert fp.p == 4 and fp._avail_rows.shape == (6000, 4)
        # inside the fail window, routing avoids the failed endpoint
        t_down = int(np.argmin(fp._avail_rows[:, 0]))
        ep, _ = fp.route(100.0, (t_down + 1) * 25.0)
        assert ep != 0
        with pytest.raises(ValueError):
            FleetProvider.from_fleet_scenario(
                scn.get_scenario("flash_crowd"), 120, 3000, 25.0, 4)
