"""`correct` has to be able to come out false.

The control (the plain reference in bfloat16, put in the program's
place) must fail a limit of each cell.  Then the rest of a run, the chip
check bypassed, with the timed path broken underneath: a tick that
leaves its state unchanged, half of the grant batch left out, and an
answer altered where it is produced.  (The cells run on one chip, so
there is no exchange between chips to leave out.)"""
import io
import os

import jax
import numpy as np
import pytest

from bench import control, harness
from bench.tests.tiny import TINY

MAN = harness.manifest()


def _files(cell, tiny=True):
    wl = next(w for w in MAN["workloads"] if w["name"] == cell)
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         wl["config"] + ".json"))
    tr = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                        wl["traffic"] + ".json"))
    if tiny:
        cfg.update(TINY[cell]["config_overrides"])
        tr.update(TINY[cell].get("traffic_overrides", {}))
    return cfg, tr


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_live_control_fails(seed):
    cfg, tr = _files("frontend.high_congestion")
    readings = control.session_control(cfg, tr, seed, 400)
    ok, _ = harness.judge(readings, cfg["check"])
    assert not ok, readings


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sim_control_fails(seed):
    """At the cell's own size: the reference runs on the CPU in seconds,
    and at a tiny horizon bfloat16 need not change an outcome."""
    cfg, tr = _files("sim.high_congestion", tiny=False)
    readings = control.scan_control(cfg, tr, seed * 1000)
    ok, _ = harness.judge(readings, cfg["check"])
    assert not ok, readings


def _run(cell):
    err = io.StringIO()
    res = harness.run(cell, 21, 0.7, False, require_tpu=False,
                      out=io.StringIO(), err=err, **TINY[cell])
    return res, err.getvalue()


@pytest.fixture
def fresh(monkeypatch):
    """Faults are planted at trace time: drop every compiled program and
    the session's tick cache before and after."""
    from repro.client import session

    monkeypatch.setattr(session, "_TICK_CACHE", {})
    jax.clear_caches()
    yield monkeypatch
    jax.clear_caches()


def _half_batch(schedule_batch):
    import jax.numpy as jnp

    def half(cfg, batch, state, max_grants=1, **kw):
        d = schedule_batch(cfg, batch, state, max_grants=max(max_grants // 2, 1),
                           **kw)
        pad = max_grants - d.actions.shape[0]
        return d._replace(
            actions=jnp.concatenate([d.actions, jnp.full((pad,), -1, jnp.int32)]),
            req_idx=jnp.concatenate([d.req_idx, jnp.zeros((pad,), jnp.int32)]),
            inflight_at=jnp.concatenate([d.inflight_at,
                                         jnp.zeros((pad,), jnp.int32)]))
    return half


def test_live_state_left_unchanged_fails(fresh):
    from repro.client import session

    fresh.setattr(session, "_apply_body", lambda policy, batch, state, *a: state)
    res, err = _run("frontend.high_congestion")
    assert not res["correct"], err


def test_live_half_batch_fails(fresh):
    from repro.client import session

    fresh.setattr(session, "schedule_batch", _half_batch(session.schedule_batch))
    res, err = _run("frontend.high_congestion")
    assert not res["correct"], err


def test_live_answer_altered_fails(fresh):
    from repro.client import session

    poll = session.ClientSession.poll

    def altered(self, *a, **kw):
        r = poll(self, *a, **kw)
        if self.stats.n_polls == 30:
            rids = r.req_rids.copy()
            rids[0] = rids[0] + 1
            r = r._replace(req_rids=rids)
        return r
    fresh.setattr(session.ClientSession, "poll", altered)
    res, err = _run("frontend.high_congestion")
    assert not res["correct"], err


def test_sim_state_left_unchanged_fails(fresh):
    from repro.sim import engine

    fresh.setattr(engine, "_apply_batch", lambda cfg, phys, batch, jitter,
                  state, d, **kw: state)
    res, err = _run("sim.high_congestion")
    assert not res["correct"], err


def test_sim_half_batch_fails(fresh):
    from repro.sim import engine

    fresh.setattr(engine, "schedule_batch", _half_batch(engine.schedule_batch))
    res, err = _run("sim.high_congestion")
    assert not res["correct"], err


def test_sim_answer_altered_fails(fresh):
    from repro.sim import runner

    compute = runner.compute_metrics

    def altered(batch, final, k):
        m = compute(batch, final, k)
        return m._replace(n_rejects=m.n_rejects + 1)
    fresh.setattr(runner, "compute_metrics", altered)
    res, err = _run("sim.high_congestion")
    assert not res["correct"], err


def test_sound_program_passes_at_the_same_sizes():
    for cell in TINY:
        res, err = _run(cell)
        assert res["correct"], err
        assert np.isfinite([c["value"] for c in res["check"].values()]).all()
