"""Active-window engine pins (DESIGN.md §6).

The contract under test: with window capacity W >= the peak live queue,
the windowed engine is *bit-exact* with the dense engine — the same
decision stream (per tick, per grant), the same final request arrays,
the same scheduler state floats — while doing O(W) work per tick
instead of O(N).  Pinned per-decision and full-horizon across
stationary and nonstationary scenarios (including provider dynamics:
brownout + token-bucket 429s), the same discipline as the B=1 and K=2
pins.

Also covered: the overflow regime (W smaller than the live queue) must
degrade gracefully — FIFO admission, no lost or duplicated requests —
and the compacted window invariants (occupied prefix, request-id
sorted) must hold tick over tick.  The window view gathers the batch's
static fields from one packed table: it must equal the view gathered
field by field bit for bit, and the compiled scan must hold one packed
gather per view in place of a gather per batch field.
"""
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.sim.engine as eng
from repro.core.policy import base_policy, kclass_policy, strategy
from repro.core.scheduler import IDLE, schedule_batch
from repro.core.types import (
    ABANDONED,
    COMPLETED,
    INFLIGHT,
    PENDING,
    REJECTED,
    RequestBatch,
    RequestState,
    init_sim_state,
    init_window_carry,
)
from repro.sim import SimConfig, WorkloadConfig, default_physics, generate, run_sim
from repro.sim import runner
from repro.sim import scenarios as scn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQ_FIELDS = ("status", "submit_ms", "finish_ms", "defer_until",
              "n_defers", "n_throttles")


def _bits_equal(a, b):
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _run_pair(policy, batch, jitter, sim_cfg, window, dynamics=None):
    phys = default_physics()
    dense = jax.jit(lambda: run_sim(
        policy, batch, jitter, phys, sim_cfg, dynamics,
        collect_decisions=True))()
    win = jax.jit(lambda: run_sim(
        policy, batch, jitter, phys, sim_cfg._replace(window=window),
        dynamics, collect_decisions=True))()
    return dense, win


def _assert_bit_exact(dense, win):
    (fd, td), (fw, tw) = dense, win
    for name in REQ_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(fd.req, name)),
            np.asarray(getattr(fw.req, name)), err_msg=name)
    assert _bits_equal(fd.sched.ema_latency_ratio, fw.sched.ema_latency_ratio)
    assert _bits_equal(fd.sched.deficit, fw.sched.deficit)
    assert int(fd.sched.rr_turn) == int(fw.sched.rr_turn)
    assert int(fd.sched.n_completed_obs) == int(fw.sched.n_completed_obs)
    assert int(fd.provider.inflight) == int(fw.provider.inflight)
    assert _bits_equal(fd.provider.tb_tokens, fw.provider.tb_tokens)
    assert int(fd.provider.n_throttled) == int(fw.provider.n_throttled)
    # per-decision stream: action, target (IDLE rows carry no target —
    # the engines encode them differently), severity bits
    a_act, w_act = np.asarray(td[0]), np.asarray(tw[0])
    np.testing.assert_array_equal(a_act, w_act)
    a_idx = np.where(a_act == IDLE, -1, np.asarray(td[1]))
    w_idx = np.where(w_act == IDLE, -1, np.asarray(tw[1]))
    np.testing.assert_array_equal(a_idx, w_idx)
    assert _bits_equal(td[2], tw[2])


class TestBitExactStationary:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_heavy_high_b4(self, seed):
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=160, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(seed), wl)
        pair = _run_pair(policy, batch, jitter,
                         SimConfig(n_ticks=2000, k_slots=4), window=192)
        _assert_bit_exact(*pair)
        # the pin must bite: work actually completed
        assert int((np.asarray(pair[0][0].req.status) == COMPLETED).sum()) > 10

    def test_b1_slot_discipline(self):
        """k_slots=1 — the windowed pass must reduce to the same
        sequential slot decisions the B=1 pins lock down."""
        policy = base_policy()
        wl = WorkloadConfig(n_requests=128, mix="balanced", congestion="medium")
        batch, jitter = generate(jax.random.PRNGKey(3), wl)
        pair = _run_pair(policy, batch, jitter,
                         SimConfig(n_ticks=2500, k_slots=1), window=128)
        _assert_bit_exact(*pair)

    @pytest.mark.slow
    def test_k4_tenants_b8(self):
        policy = kclass_policy(4)
        wl = WorkloadConfig(n_requests=200, mix="heavy", congestion="high",
                            class_map="tenant4")
        batch, jitter = generate(jax.random.PRNGKey(4), wl)
        pair = _run_pair(policy, batch, jitter,
                         SimConfig(n_ticks=2500, k_slots=8), window=256)
        _assert_bit_exact(*pair)


class TestBitExactNonstationary:
    @pytest.mark.parametrize("name", ["flash_crowd", "storm"])
    def test_scenario(self, name):
        """Nonstationary arrivals + provider dynamics (storm: brownout
        AND token-bucket 429s at once) — the windowed engine must
        reproduce the dense decision stream through every mechanism."""
        sc = scn.get_scenario(name)
        sim_cfg = SimConfig(n_ticks=3000, k_slots=4)
        wl, sched, dyn, _ = scn.build(sc, 160, sim_cfg.n_ticks,
                                      sim_cfg.dt_ms, limiter_classes=2)
        batch, jitter = generate(jax.random.PRNGKey(0), wl, sched)
        policy = strategy("final_adrr_olc")
        pair = _run_pair(policy, batch, jitter, sim_cfg, window=256,
                         dynamics=dyn)
        _assert_bit_exact(*pair)

    def test_rate_limited_throttles_match(self):
        """429 bounces flow through the window translation: the per-
        request throttle counts and bucket state must stay bit-exact."""
        sc = scn.get_scenario("rate_limited")
        sim_cfg = SimConfig(n_ticks=3000, k_slots=4)
        wl, sched, dyn, _ = scn.build(sc, 160, sim_cfg.n_ticks,
                                      sim_cfg.dt_ms, limiter_classes=2)
        batch, jitter = generate(jax.random.PRNGKey(1), wl, sched)
        pair = _run_pair(strategy("final_adrr_olc"), batch, jitter, sim_cfg,
                         window=256, dynamics=dyn)
        _assert_bit_exact(*pair)
        assert int(pair[0][0].provider.n_throttled) > 0  # limiter bit


class TestWindowInternals:
    def _drive(self, w, n_ticks=400, n_req=96):
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=n_req, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(5), wl)
        phys = default_physics()
        state = init_sim_state(batch.n, 2)
        win = init_window_carry(w, batch.n)

        @jax.jit
        def tick(state, win, t):
            now = (t + 1.0) * 25.0
            state = state._replace(now_ms=now)
            state, alive = eng._retire_window(policy, phys, batch, state, win)
            win = eng._compact_and_admit(batch, win, alive, now)
            wb, wr, _ = eng._window_view(batch, state.req, win.slot_req)
            d = schedule_batch(policy, wb, state._replace(req=wr),
                               max_grants=4)
            d = d._replace(req_idx=win.slot_req[jnp.clip(d.req_idx, 0, w - 1)])
            state = eng._apply_batch(policy, phys, batch, jitter, state, d)
            return state, win

        traj = []
        for t in range(n_ticks):
            state, win = tick(state, win, jnp.float32(t))
            traj.append(np.asarray(win.slot_req))
        return batch, state, win, traj

    def test_compaction_invariants(self):
        """Occupied slots form a request-id-sorted prefix every tick —
        the property the first-occurrence tie-breaking proof rests on."""
        batch, _, _, traj = self._drive(w=128)
        n = batch.n
        for slots in traj[::7]:
            occ = slots < n
            k = occ.sum()
            assert occ[:k].all() and not occ[k:].any()  # compacted prefix
            ids = slots[:k]
            assert (np.diff(ids) > 0).all()             # strictly sorted
            assert (slots[k:] == n).all()               # empty sentinel

    def test_overflow_conserves_requests(self):
        """W far below the live queue: admission throttles FIFO, but no
        request is lost, duplicated, or granted before arrival."""
        w = 16
        batch, state, win, traj = self._drive(w=w, n_ticks=600)
        n = batch.n
        for slots in traj[::11]:
            ids = slots[slots < n]
            assert len(set(ids.tolist())) == len(ids)   # no duplicates
        st = np.asarray(state.req.status)
        assert set(np.unique(st)) <= {PENDING, INFLIGHT, COMPLETED,
                                      REJECTED, ABANDONED}
        sub = np.asarray(state.req.submit_ms)
        arr = np.asarray(batch.arrival_ms)
        sent = np.isfinite(sub)
        assert (sub[sent] >= arr[sent]).all()
        # the tiny window still moved real work through the provider
        assert int((st == COMPLETED).sum()) > 0

    def test_overflow_full_run_terminates(self):
        """run_sim end-to-end with an undersized window: the drain must
        still account every request to a terminal state."""
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=120, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(6), wl)
        final = jax.jit(lambda: run_sim(
            policy, batch, jitter, default_physics(),
            SimConfig(n_ticks=3000, k_slots=4, window=24)))()
        st = np.asarray(final.req.status)
        assert ((st == COMPLETED) | (st == REJECTED)
                | (st == ABANDONED)).all()


class TestWindowedPallasBackend:
    def test_dispatch_parity_non_lane_aligned_window(self):
        """The pallas ordering backend inside window mode at W not a
        multiple of the TPU lane width (padding path in
        kernels/sched_score/ops.py): decisions must match the jnp
        backend for the same window view."""
        policy = strategy("final_adrr_olc")
        wl = WorkloadConfig(n_requests=160, mix="heavy", congestion="high")
        batch, jitter = generate(jax.random.PRNGKey(7), wl)
        phys = default_physics()
        w = 96  # not a multiple of 128
        state = init_sim_state(batch.n, 2)
        win = init_window_carry(w, batch.n)

        @jax.jit
        def advance(state, win, t):
            now = (t + 1.0) * 25.0
            state = state._replace(now_ms=now)
            state, alive = eng._retire_window(policy, phys, batch, state, win)
            win = eng._compact_and_admit(batch, win, alive, now)
            wb, wr, _ = eng._window_view(batch, state.req, win.slot_req)
            d = schedule_batch(policy, wb, state._replace(req=wr),
                               max_grants=4)
            d = d._replace(req_idx=win.slot_req[jnp.clip(d.req_idx, 0, w - 1)])
            state = eng._apply_batch(policy, phys, batch, jitter, state, d)
            return state, win

        checked = 0
        for t in range(160):
            state, win = advance(state, win, jnp.float32(t))
            if t % 40 == 17:
                wb, wr, _ = eng._window_view(batch, state.req, win.slot_req)
                ws = state._replace(
                    now_ms=jnp.float32((t + 1.5) * 25.0), req=wr)
                dj = jax.jit(schedule_batch, static_argnames=(
                    "max_grants", "backend"))(
                    policy, wb, ws, max_grants=4, backend="jnp")
                dp = jax.jit(schedule_batch, static_argnames=(
                    "max_grants", "backend"))(
                    policy, wb, ws, max_grants=4, backend="pallas")
                np.testing.assert_array_equal(
                    np.asarray(dj.actions), np.asarray(dp.actions))
                live = np.asarray(dj.actions) != IDLE
                np.testing.assert_array_equal(
                    np.asarray(dj.req_idx)[live], np.asarray(dp.req_idx)[live])
                checked += 1
        assert checked >= 3


class TestRunnerThreading:
    def test_run_cell_windowed_matches_dense(self):
        """The seed-vmapped runner path (metrics included) is identical
        under the windowed engine — window is purely an execution
        strategy, invisible in results.  Sized via the exported
        `window_for` heuristic (which must clear the bit-exactness
        condition here: its floor exceeds this population outright)."""
        from repro.sim import run_cell, window_for
        policy = base_policy()
        wl = WorkloadConfig(n_requests=96, mix="balanced", congestion="medium")
        w = window_for(wl.n_requests)
        assert w >= wl.n_requests  # floor covers small populations
        m_dense = run_cell(policy, wl, seeds=2,
                           sim_cfg=SimConfig(n_ticks=1500, k_slots=4))
        m_win = run_cell(policy, wl, seeds=2,
                         sim_cfg=SimConfig(n_ticks=1500, k_slots=4,
                                           window=w))
        for name in ("global_p95_ms", "completion_rate", "satisfaction",
                     "goodput_rps", "n_rejects", "n_abandoned",
                     "class_p95_ms"):
            a = np.asarray(getattr(m_dense, name))
            b = np.asarray(getattr(m_win, name))
            np.testing.assert_array_equal(a[np.isfinite(a)], b[np.isfinite(b)],
                                          err_msg=name)


def _per_field_view(batch, req, slot_req):
    """The window view gathered one field at a time: the reference the
    packed gather must reproduce bit for bit."""
    n = batch.n
    occ = slot_req < n
    safe = jnp.minimum(slot_req, n - 1)
    wb = RequestBatch(*(f[safe] for f in batch))
    wb = wb._replace(valid=wb.valid & occ)
    wr = RequestState(
        status=jnp.where(occ, req.status[safe], jnp.int32(REJECTED)),
        submit_ms=req.submit_ms[safe],
        finish_ms=jnp.where(occ, req.finish_ms[safe], jnp.inf),
        defer_until=req.defer_until[safe],
        n_defers=req.n_defers[safe],
        n_throttles=req.n_throttles[safe],
        endpoint=None if req.endpoint is None else req.endpoint[safe],
    )
    return wb, wr, occ


def _raw(x):
    """The array's bits: floats as their int32 view."""
    a = np.asarray(x)
    return a.view(np.int32) if a.dtype == np.float32 else a


class TestPackedWindowView:
    N, W = 40, 32

    def _case(self, n_live=None, inf_finish=False, odd_floats=False,
              padded=False, fleet_p=None):
        n, w = self.N, self.W
        rng = np.random.default_rng(8)
        batch, _ = generate(jax.random.PRNGKey(8), WorkloadConfig(
            n_requests=n, mix="heavy", congestion="high"))
        valid = np.ones(n, bool)
        if padded:
            valid[n // 2:] = False
        batch = batch._replace(valid=jnp.asarray(valid))
        n_live = w if n_live is None else n_live
        ids = np.sort(rng.choice(n, n_live, replace=False))
        if odd_floats:  # in rows the window holds
            tiny = np.float32(1e-40)  # subnormal: a move must keep it
            batch = batch._replace(
                arrival_ms=batch.arrival_ms.at[ids[1]].set(-0.0),
                p50=batch.p50.at[ids[2]].set(tiny),
                deadline_budget_ms=batch.deadline_budget_ms.at[ids[3]].set(
                    -tiny))
        finish = rng.uniform(0, 5e4, n).astype(np.float32)
        if inf_finish:
            finish[::3] = np.inf
        req = RequestState(
            status=jnp.asarray(rng.integers(0, 5, n), jnp.int32),
            submit_ms=jnp.asarray(rng.uniform(0, 5e4, n), jnp.float32),
            finish_ms=jnp.asarray(finish),
            defer_until=jnp.asarray(rng.uniform(0, 5e4, n), jnp.float32),
            n_defers=jnp.asarray(rng.integers(0, 3, n), jnp.int32),
            n_throttles=jnp.asarray(rng.integers(0, 3, n), jnp.int32),
            endpoint=None if fleet_p is None else jnp.asarray(
                rng.integers(0, fleet_p, n), jnp.int32),
        )
        slot_req = np.full(w, n, np.int32)
        slot_req[:n_live] = ids
        return batch, req, jnp.asarray(slot_req)

    @pytest.mark.parametrize("case", [
        dict(n_live=20),                  # live window, empty sentinel slots
        dict(inf_finish=True),
        dict(odd_floats=True, n_live=20),  # -0.0 and subnormals
        dict(),                           # all valid, full window
        dict(padded=True),                # padding rows inside the window
        dict(fleet_p=16, n_live=20),      # fleet state with endpoints
    ], ids=["sentinel_slots", "inf_finish", "neg_zero_subnormal",
            "all_valid", "padded_valid", "fleet_endpoint_p16"])
    def test_packed_view_equals_per_field_view(self, case):
        batch, req, slot_req = self._case(**case)
        want = jax.jit(_per_field_view)(batch, req, slot_req)
        for table in (None, eng.pack_batch(batch)):
            got = jax.jit(eng._window_view)(batch, req, slot_req, table)
            for part_w, part_g in zip(want, got):
                if isinstance(part_w, tuple):
                    assert part_g._fields == part_w._fields
                    for name, a, b in zip(part_w._fields, part_w, part_g):
                        assert (a is None) == (b is None), name
                        if a is not None:
                            assert np.asarray(a).dtype == np.asarray(b).dtype
                            assert np.array_equal(_raw(a), _raw(b)), name
                else:
                    assert np.array_equal(_raw(part_w), _raw(part_g))
        if case.get("odd_floats"):  # the odd values reached the window
            assert (_raw(want[0].arrival_ms)
                    == np.float32(-0.0).view(np.int32)).any()
            assert (_raw(want[0].p50) == np.float32(1e-40).view(np.int32)).any()


def test_scan_gathers_the_batch_once_per_window_view():
    """The `sim.high_congestion` program (its configuration and traffic
    under bench/, cut to a few ticks), compiled on the CPU: inside the
    scan, each window view (retire's and admission's) gathers the batch
    with one gather of `len(RequestBatch._fields)` values per slot; the
    only other window-width gathers from the per-request arrays are those
    of the request-state fields the stages read, one each."""
    with open(os.path.join(ROOT, "bench/configs/sim_paper_n160.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench/traffic/high_congestion.json")) as f:
        tr = json.load(f)
    s, w = int(cfg["seeds_per_call"]), int(cfg["window"])
    sim_cfg = SimConfig(dt_ms=float(cfg["dt_ms"]), n_ticks=4,
                        k_slots=int(cfg["k_slots"]),
                        ordering_backend=cfg["backend"], window=w)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(0, s))
    # the policy's values are operands: they do not shape the program
    text = runner._run_scenario_seeds.lower(
        strategy("final_adrr_olc"), default_physics(**cfg["provider"]),
        keys, scn.get_scenario(tr["scenario"]), sim_cfg,
        int(cfg["n_requests"]), tr["class_map"], tr["information"],
        1.0).compile().as_text()
    n_fields = len(RequestBatch._fields)
    found = {"tick.retire": [], "tick.admit": []}
    for m in re.finditer(
            r"= \w+\[(\d+)[^\]]*\]\S* gather\(.*?slice_sizes=\{([\d,]+)\}"
            r'.*?op_name="[^"]*/while/body/[^"]*?(tick\.retire|tick\.admit)'
            r'/gather"', text):
        width, sizes, stage = int(m[1]), m[2].split(","), m[3]
        # from the (seeds, N) arrays and the (seeds, 8, N) table into the
        # seeds x W window; values moved per slot = the slice's size
        if width == s * w and len(sizes) >= 2:
            found[stage].append(math.prod(int(x) for x in sizes))
    # status and finish (retire); status, defer_until, n_defers (admit)
    req_read = {"tick.retire": 2, "tick.admit": 3}
    for stage, sizes in found.items():
        assert sorted(sizes) == [1] * req_read[stage] + [n_fields], (
            stage, sizes)
