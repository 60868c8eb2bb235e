"""Unit + property tests for the three scheduler layers (repro.core)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import drr, ordering, overload
from repro.core.policy import (
    base_policy,
    strategy,
    with_bucket_policy,
    with_information,
)
from repro.core.scheduler import (
    IDLE,
    BatchDecision,
    apply_verdicts,
    schedule_slot,
)
from repro.core.types import (
    INFLIGHT,
    PENDING,
    REJECTED,
    RequestBatch,
    SHORT,
    XLONG,
    init_sim_state,
)


def mk_batch(n=8, arrival=None, bucket=None, p50=None):
    arrival = jnp.asarray(arrival if arrival is not None else np.arange(n) * 10.0, jnp.float32)
    bucket = jnp.asarray(bucket if bucket is not None else np.zeros(n), jnp.int32)
    p50 = jnp.asarray(p50 if p50 is not None else np.full(n, 100.0), jnp.float32)
    cls = jnp.where(bucket == SHORT, 0, 1).astype(jnp.int32)
    return RequestBatch(
        arrival_ms=arrival,
        bucket=bucket,
        cls=cls,
        true_tokens=p50,
        p50=p50,
        p90=p50 * 1.8,
        deadline_budget_ms=jnp.full((n,), 5000.0, jnp.float32),
        valid=jnp.ones((n,), bool),
    )


# ---------------------------------------------------------------------------
# Layer 2: ordering
# ---------------------------------------------------------------------------

class TestOrdering:
    def test_fifo_picks_earliest(self):
        b = mk_batch(4, arrival=[30.0, 10.0, 20.0, 40.0])
        idx, ok = ordering.select_fifo(b, jnp.ones(4, bool))
        assert bool(ok) and int(idx) == 1

    def test_fifo_respects_mask(self):
        b = mk_batch(4, arrival=[30.0, 10.0, 20.0, 40.0])
        idx, ok = ordering.select_fifo(b, jnp.asarray([True, False, False, True]))
        assert bool(ok) and int(idx) == 0

    def test_empty_mask_not_ok(self):
        b = mk_batch(4)
        _, ok = ordering.select_fifo(b, jnp.zeros(4, bool))
        assert not bool(ok)

    def test_score_prefers_older_and_smaller(self):
        cfg = base_policy()
        # two heavy jobs, same arrival: smaller wins
        b = mk_batch(2, arrival=[0.0, 0.0], bucket=[2, 2], p50=[2000.0, 300.0])
        idx, ok = ordering.select_scored(b, jnp.ones(2, bool), jnp.float32(1000.0), cfg)
        assert bool(ok) and int(idx) == 1
        # same size, older wins
        b = mk_batch(2, arrival=[0.0, 900.0], bucket=[2, 2], p50=[300.0, 300.0])
        idx, _ = ordering.select_scored(b, jnp.ones(2, bool), jnp.float32(1000.0), cfg)
        assert int(idx) == 0

    def test_urgency_overrides_size(self):
        cfg = base_policy(ord_w_urg=jnp.float32(50.0))
        b = mk_batch(2, arrival=[0.0, 0.0], bucket=[2, 2], p50=[2000.0, 300.0])
        # request 0 about to blow its deadline
        b = b._replace(deadline_budget_ms=jnp.asarray([1000.0, 99000.0], jnp.float32))
        idx, _ = ordering.select_scored(b, jnp.ones(2, bool), jnp.float32(990.0), cfg)
        assert int(idx) == 0

    def test_eligibility_excludes_future_and_deferred(self):
        b = mk_batch(3, arrival=[0.0, 100.0, 0.0])
        status = jnp.zeros(3, jnp.int32)
        defer_until = jnp.asarray([0.0, 0.0, 500.0], jnp.float32)
        el = ordering.eligibility(b, status, defer_until, jnp.float32(50.0))
        assert el.tolist() == [True, False, False]


# ---------------------------------------------------------------------------
# Layer 3: overload
# ---------------------------------------------------------------------------

class TestOverload:
    def test_severity_zero_when_idle(self):
        cfg = base_policy()
        s = overload.severity_score(
            cfg, inflight_total=0, n_pending=0, ema_latency_ratio=jnp.float32(1.0))
        assert float(s) == pytest.approx(0.0, abs=1e-5)

    def test_short_never_rejected_under_ladder(self):
        cfg = base_policy()
        for sev in [0.0, 0.5, 0.9, 5.0]:
            a = overload.admission_action(
                cfg, severity=jnp.float32(sev), bucket=jnp.int32(SHORT),
                n_defers=jnp.int32(0))
            assert int(a) == overload.ADMIT

    def test_ladder_progression_xlong(self):
        cfg = base_policy()
        acts = [
            int(overload.admission_action(
                cfg, severity=jnp.float32(s), bucket=jnp.int32(XLONG),
                n_defers=jnp.int32(0)))
            for s in [0.2, 0.5, 0.7]
        ]
        assert acts == [overload.ADMIT, overload.DEFER, overload.REJECT]

    def test_long_rejected_later_than_xlong(self):
        cfg = base_policy()
        a_long = int(overload.admission_action(
            cfg, severity=jnp.float32(0.7), bucket=jnp.int32(2), n_defers=jnp.int32(0)))
        assert a_long == overload.DEFER  # long defers where xlong rejects

    def test_disabled_olc_always_admits(self):
        cfg = base_policy(olc_enabled=jnp.float32(0.0))
        a = overload.admission_action(
            cfg, severity=jnp.float32(9.0), bucket=jnp.int32(XLONG), n_defers=jnp.int32(0))
        assert int(a) == overload.ADMIT

    def test_defer_exhaustion_admits(self):
        cfg = base_policy()
        a = overload.admission_action(
            cfg, severity=jnp.float32(0.5), bucket=jnp.int32(XLONG),
            n_defers=jnp.int32(99))
        assert int(a) == overload.ADMIT

    @given(sev=st.floats(0, 3), bucket=st.integers(0, 3), nd=st.integers(0, 5))
    @settings(max_examples=60, deadline=None)
    def test_property_monotone_in_severity(self, sev, bucket, nd):
        """Raising severity never produces a milder action."""
        cfg = base_policy()
        a1 = int(overload.admission_action(
            cfg, severity=jnp.float32(sev), bucket=jnp.int32(bucket), n_defers=jnp.int32(nd)))
        a2 = int(overload.admission_action(
            cfg, severity=jnp.float32(sev + 0.3), bucket=jnp.int32(bucket), n_defers=jnp.int32(nd)))
        order = {overload.ADMIT: 0, overload.DEFER: 1, overload.REJECT: 2}
        # exhausted defers collapse DEFER->ADMIT; treat that as equivalent
        if nd < 2:
            assert order[a2] >= order[a1]

    @given(sev=st.floats(0, 3))
    @settings(max_examples=40, deadline=None)
    def test_property_every_shape_spares_short(self, sev):
        for shape in ["ladder", "uniform_mild", "uniform_harsh", "reverse"]:
            cfg = with_bucket_policy(base_policy(), shape)
            a = int(overload.admission_action(
                cfg, severity=jnp.float32(sev), bucket=jnp.int32(SHORT), n_defers=jnp.int32(0)))
            assert a == overload.ADMIT


# ---------------------------------------------------------------------------
# Layer 1: allocation
# ---------------------------------------------------------------------------

def alloc_args(**kw):
    d = dict(
        backlog=jnp.asarray([1, 1], jnp.int32),
        head_cost=jnp.asarray([50.0, 500.0], jnp.float32),
        inflight_cls=jnp.asarray([0, 0], jnp.int32),
        inflight_total=jnp.int32(0),
        severity=jnp.float32(0.0),
        deficit=jnp.asarray([1000.0, 1000.0], jnp.float32),
        rr_turn=jnp.int32(0),
    )
    d.update(kw)
    return d


class TestAllocation:
    def test_adrr_work_conserving(self):
        """An empty interactive class never blocks heavy dispatch."""
        cfg = strategy("adaptive_drr")
        c = drr.allocate(cfg, **alloc_args(backlog=jnp.asarray([0, 1], jnp.int32)))
        assert bool(c.send_ok) and int(c.cls_id) == 1

    def test_adrr_insufficient_deficit_blocks(self):
        cfg = strategy("adaptive_drr")
        c = drr.allocate(cfg, **alloc_args(
            backlog=jnp.asarray([0, 1], jnp.int32),
            head_cost=jnp.asarray([jnp.inf, 1e9], jnp.float32),
            deficit=jnp.asarray([0.0, 0.0], jnp.float32)))
        assert not bool(c.send_ok)
        # ... but deficit accrued for the backlogged class
        assert float(c.deficit[1]) > 0

    def test_adrr_deficit_charged_on_send(self):
        cfg = strategy("adaptive_drr")
        c = drr.allocate(cfg, **alloc_args(backlog=jnp.asarray([0, 1], jnp.int32)))
        assert bool(c.send_ok)
        assert float(c.deficit[1]) < 1000.0 + float(cfg.drr_quantum) * 2

    def test_adrr_heavy_cap_blocks_heavy_only(self):
        cfg = strategy("adaptive_drr")
        c = drr.allocate(cfg, **alloc_args(
            inflight_cls=jnp.asarray([0, 99], jnp.int32)))
        assert bool(c.send_ok) and int(c.cls_id) == 0

    def test_severity_biases_interactive(self):
        cfg = strategy("adaptive_drr")
        w0 = drr.effective_weights(cfg, jnp.float32(0.0))
        w1 = drr.effective_weights(cfg, jnp.float32(1.0))
        assert float(w1[0] / w1[1]) > float(w0[0] / w0[1])

    def test_quota_strands_heavy_beyond_quota(self):
        cfg = strategy("quota_tiered")
        # heavy inflight at its quota (class_cap[1] = 3) => no send
        c = drr.allocate(cfg, **alloc_args(
            backlog=jnp.asarray([0, 5], jnp.int32),
            inflight_cls=jnp.asarray([0, 3], jnp.int32)))
        assert not bool(c.send_ok)

    def test_fq_alternates(self):
        cfg = strategy("fair_queuing")
        c0 = drr.allocate(cfg, **alloc_args())
        c1 = drr.allocate(cfg, **alloc_args(rr_turn=c0.rr_turn))
        assert int(c0.cls_id) != int(c1.cls_id)

    def test_sp_prefers_short(self):
        cfg = strategy("short_priority")
        c = drr.allocate(cfg, **alloc_args())
        assert int(c.cls_id) == 0

    def test_naive_ignores_class(self):
        cfg = strategy("direct_naive")
        c = drr.allocate(cfg, **alloc_args())
        assert bool(c.ignore_class) and bool(c.send_ok)

    # the slow mark sits *above* @given: the hypothesis fallback shim's
    # wrapper does not propagate pytestmark from the wrapped function
    @pytest.mark.slow
    @given(
        b0=st.integers(0, 3), b1=st.integers(0, 3),
        sev=st.floats(0, 1.5), d0=st.floats(0, 3000), d1=st.floats(0, 3000),
    )
    @settings(max_examples=60, deadline=None)
    def test_property_send_implies_backlog(self, b0, b1, sev, d0, d1):
        """Whatever the mode, a selected class must actually have work."""
        for name in ["adaptive_drr", "fair_queuing", "short_priority", "quota_tiered"]:
            cfg = strategy(name)
            c = drr.allocate(cfg, **alloc_args(
                backlog=jnp.asarray([b0, b1], jnp.int32),
                severity=jnp.float32(sev),
                deficit=jnp.asarray([d0, d1], jnp.float32)))
            if bool(c.send_ok):
                assert [b0, b1][int(c.cls_id)] > 0


# ---------------------------------------------------------------------------
# Fused slot (layers composed)
# ---------------------------------------------------------------------------

class TestScheduleSlot:
    def test_slot_selects_feasible_request(self):
        """Paper: zero violations of the ordering layer's feasibility
        constraints — the released request is always arrived+pending."""
        cfg = strategy("final_adrr_olc")
        b = mk_batch(6, arrival=[0, 0, 50, 5000, 0, 0],
                     bucket=[0, 2, 0, 0, 3, 1])
        st0 = init_sim_state(6)._replace(now_ms=jnp.float32(100.0))
        d = schedule_slot(cfg, b, st0)
        assert int(d.action) != IDLE
        i = int(d.req_idx)
        assert float(b.arrival_ms[i]) <= 100.0

    def test_idle_when_nothing_eligible(self):
        cfg = strategy("final_adrr_olc")
        b = mk_batch(3, arrival=[1000.0, 2000.0, 3000.0])
        st0 = init_sim_state(3)._replace(now_ms=jnp.float32(10.0))
        d = schedule_slot(cfg, b, st0)
        assert int(d.action) == IDLE

    def test_no_info_single_lane(self):
        cfg = with_information(strategy("final_adrr_olc"), "no_info")
        b = mk_batch(4, bucket=[0, 3, 2, 1])
        from repro.core.scheduler import effective_class
        assert effective_class(cfg, b).tolist() == [0, 0, 0, 0]

    def test_jit_and_vmap_compile(self):
        cfg = strategy("final_adrr_olc")
        b = mk_batch(8)
        st0 = init_sim_state(8)._replace(now_ms=jnp.float32(100.0))
        d = jax.jit(schedule_slot)(cfg, b, st0)
        assert d.action.shape == ()


# ---------------------------------------------------------------------------
# The verdict transition both drivers share
# ---------------------------------------------------------------------------

class TestApplyVerdicts:
    def test_each_verdict_scatters_once(self):
        """One batch with an admit, a defer, a reject, a 429 bounce and
        an IDLE row: each verdict writes its own fields of its own row
        and nothing else; without a bounce the deficits come back
        bit-unchanged, a -0.0 entry included."""
        cfg = strategy("final_adrr_olc")
        b = mk_batch(6, bucket=[0, 2, 3, 1, 0, 2],
                     p50=[100.0, 200.0, 300.0, 400.0, 50.0, 60.0])
        st = init_sim_state(6)._replace(now_ms=jnp.float32(1000.0))
        st = st._replace(req=st.req._replace(
            n_defers=jnp.asarray([0, 1, 0, 0, 0, 0], jnp.int32)))
        act = jnp.asarray([overload.ADMIT, overload.DEFER, overload.REJECT,
                           overload.ADMIT, IDLE], jnp.int32)
        d = BatchDecision(
            actions=act, req_idx=jnp.asarray([0, 1, 2, 3, 4], jnp.int32),
            inflight_at=jnp.zeros((5,), jnp.int32),
            severity=jnp.float32(0.5),
            deficit=jnp.asarray([-0.0, 7.0], jnp.float32),
            rr_turn=jnp.int32(1))
        admit = act == overload.ADMIT
        bounced = jnp.asarray([False, False, False, True, False])
        retry = jnp.asarray([0.0, 0.0, 0.0, 1500.0, 0.0], jnp.float32)
        finish = jnp.full((5,), 1234.0, jnp.float32)
        out = apply_verdicts(cfg, b, st, d, admit & ~bounced, bounced,
                             retry, finish=finish)
        r = out.req
        assert r.status.tolist() == [INFLIGHT, PENDING, REJECTED, PENDING,
                                     PENDING, PENDING]
        inf = float("inf")
        assert r.submit_ms.tolist() == [1000.0] + [inf] * 5
        assert r.finish_ms.tolist() == [1234.0] + [inf] * 5
        backoff = float(overload.defer_backoff(cfg, d.severity, 1))
        assert r.defer_until.tolist() == [0.0, 1000.0 + backoff, 0.0,
                                          2500.0, 0.0, 0.0]
        assert r.n_defers.tolist() == [0, 2, 0, 0, 0, 0]
        assert r.n_throttles.tolist() == [0, 0, 0, 1, 0, 0]
        # the bounce refunds its class's charge (request 3 is class 1)
        assert out.sched.deficit.tolist() == [0.0, 407.0]
        assert int(out.sched.rr_turn) == 1
        assert int(out.provider.n_throttled) == 1
        assert int(out.provider.inflight) == 1
        assert float(out.provider.inflight_tokens) == 100.0

        for no_bounce in (jnp.zeros((5,), bool), None):
            out = apply_verdicts(cfg, b, st, d, admit, no_bounce, 0.0)
            assert out.sched.deficit.tobytes() == d.deficit.tobytes()
            assert int(out.provider.n_throttled) == 0
            assert int(out.provider.inflight) == 2
            # without `finish` the transport owns the landing time
            assert np.isinf(np.asarray(out.req.finish_ms)).all()
