"""Tick-driven discrete-event engine, written as one `lax.scan`.

Hardware-adaptation note (DESIGN.md §3): the paper's simulator is an
implicit Python event loop; re-expressing it as a fixed-shape JAX scan
makes every policy sweep a single compiled program that `vmap`s over
seeds, regimes and stacked PolicyConfigs — this is what lets the full
benchmark suite (hundreds of runs) execute in seconds on one host and
would let a TPU host run thousands of what-if schedules per second
alongside the serving mesh.

Each tick:
  1. completions  (finish_ms <= now)  -> COMPLETED, update tail EMA
  2. timeouts     (pending too long)  -> ABANDONED (the implicit failure
                                         mode explicit shedding replaces)
  3. ONE batched dispatch pass (`schedule_batch`, DESIGN.md §3): up to
     `k_slots` grants from a single vectorized allocation -> ordering ->
     overload evaluation, applied as one scatter.  The per-tick policy
     cost is O(K·N + B·K) instead of the O(B·K·N) the former sequential
     slot loop paid; with k_slots=1 the tick is bit-exact with the
     sequential `schedule_slot` path.

Nonstationary provider dynamics (DESIGN.md §5): `run_sim` optionally
takes a `ProviderDynamics` whose (T,)-shaped schedules ride the scan as
xs — brownout comfort scaling applied to the tick's admissions, and a
per-class token-bucket rate limiter at the provider boundary whose
429-style bounces return the request to PENDING with a client-visible
retry-after.  Presence of each mechanism is pytree structure (None =
off), so scenario complexity costs nothing at trace time: the whole
horizon stays one `lax.scan` with no Python per-tick branching, and
`dynamics=None` compiles the exact stationary program.

Active window (DESIGN.md §6): with `SimConfig.window = W` the scan
carries a compacted `(W,)` slot pool (`WindowCarry`) holding exactly the
live queue — arrived, non-terminal requests.  Each tick retires
completed/rejected/abandoned slots (scattering their terminal outcome
into the dense `(N,)` result arrays, which stay in the carry and are
updated in place), compacts the survivors, admits newly-arrived
requests off the arrival-sorted stream with one O(log N) bisect, and
runs the *same* `schedule_batch` on the `(K, W)` window view.  Per-tick
policy cost is O(W), independent of the horizon population N; with
W >= the peak live queue the decision stream and final request arrays
are bit-exact with the dense engine (the pinned contract —
tests/test_window_engine.py).

Fleet axis (DESIGN.md §10): `run_sim(..., fleet=Fleet(phys, dyn))`
stacks provider physics along a `(P,)` axis and runs the layer-0
routing pass (`core.routing`) before dispatch — every grant carries an
endpoint, service is priced against that endpoint's own inflight load,
the rate limiter becomes a `(P, K)` bucket grid, and a dead endpoint's
in-flight work is requeued (PENDING + Retry-After defer + throttle
bump) before completions are computed.  Like every other optional
mechanism, `fleet=None` is pytree structure: the fleet-free program is
byte-identical to the pre-fleet engine, and at static P=1 the fleet
engine takes scalar-gather branches that reproduce the single-provider
arithmetic bit-for-bit (tests/test_fleet.py pins both).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import overload as olc
from repro.core import stages
from repro.core.numerics import pinned
from repro.core.policy import ALLOC_ADRR, PolicyConfig, n_classes
from repro.core.scheduler import BatchDecision, schedule_batch
from repro.core.routing import route_requests
from repro.core.types import (
    ABANDONED,
    COMPLETED,
    INFLIGHT,
    PENDING,
    REJECTED,
    RequestBatch,
    RequestState,
    SimState,
    WindowCarry,
    init_fleet_state,
    init_sim_state,
    init_window_carry,
)
from repro.sim.provider import (
    Fleet,
    ProviderDynamics,
    ProviderPhysics,
    service_time_ms,
    unloaded_latency_ms,
)

EMA_ALPHA = 0.15

# Canonical width of the per-tick EMA completion sample (see
# `_completed_ratio_sum`).  Far above per-tick completion counts any
# regime produces; both engine representations truncate identically.
EMA_SAMPLE_CAP = 128


class SimConfig(NamedTuple):
    dt_ms: float = 25.0
    n_ticks: int = 6000
    k_slots: int = 4  # max grants per tick (batch dispatch width B)
    ordering_backend: str = "jnp"  # "jnp" | "pallas" (large-N path)
    window: Optional[int] = None  # active-window capacity W; None = dense
                                  # O(N) scan (requires arrival-sorted
                                  # batches when set — the generator's
                                  # native order)


def _completed_ratio_sum(
    phys: ProviderPhysics,
    done_now: jnp.ndarray,
    finish_ms: jnp.ndarray,
    arrival_ms: jnp.ndarray,
    tokens: jnp.ndarray,
):
    """Shape-canonical tail-EMA contribution of this tick's completions.

    The windowed and dense engines hold the completions in
    different-width arrays ((W,) vs (N,)), and both XLA's reduction tree
    and its instruction selection for fused elementwise chains (FMA
    contraction, reciprocal-based division) depend on the surrounding
    program — so computing `sum(e2e / expected)` over the wide arrays
    rounds differently in the two engines and breaks their bit-exact
    contract.  Both engines therefore extract the completing entries
    into fixed `(EMA_SAMPLE_CAP,)` buffers in index order (request-id
    order in both: the window is compaction-sorted by request id) and
    run the *entire* ratio arithmetic on those — the optimization
    barrier cuts fusion with the differently-shaped producers, so the
    subgraph between gather and sum is the same program in both engines
    and rounds identically by construction.  Past the cap both
    representations truncate to the same first `EMA_SAMPLE_CAP`
    completions (the cap is far above per-tick completion counts any
    regime produces).  Returns (ratio_sum, count).
    """
    c = EMA_SAMPLE_CAP
    idx, = jnp.nonzero(done_now, size=c, fill_value=0)
    k = done_now.sum()
    fin, arr, tok, live = pinned((
        finish_ms[idx], arrival_ms[idx], tokens[idx], jnp.arange(c) < k,
    ))
    e2e = fin - arr
    expected = unloaded_latency_ms(phys, tok)
    ratio = jnp.where(live, e2e / jnp.maximum(expected, 1.0), 0.0)
    # the inputs above are already routed through pinned(), so this sum
    # runs inside the isolated subgraph; wrapping it again would change
    # the fused HLO and break the committed windowed/dense parity pins
    return ratio.sum(), k  # reprolint: disable=RPL001


@stages.scoped(stages.RETIRE)
def _complete_and_timeout(
    cfg: PolicyConfig,
    phys: ProviderPhysics,
    batch: RequestBatch,
    state: SimState,
    avail_t=None,
    retry_after_ms=None,
) -> SimState:
    req = state.req
    now = state.now_ms

    finish_ms = req.finish_ms
    defer_until = req.defer_until
    n_throttles = req.n_throttles
    status0 = req.status
    n_requeue_ep = None
    if avail_t is not None:
        # fleet failover: a down endpoint kills its in-flight work before
        # any of it can land this tick — the client observes the drop and
        # requeues with the provider's Retry-After backoff.  (The live
        # `FleetProvider` drains gracefully instead; the engine models
        # the harsher abrupt-kill failure, see DESIGN.md §10.)
        ep = req.endpoint
        down = jnp.asarray(avail_t, jnp.float32)[ep] < 0.5
        requeue = (status0 == INFLIGHT) & down
        status0 = jnp.where(requeue, PENDING, status0)
        finish_ms = jnp.where(requeue, jnp.inf, finish_ms)
        defer_until = jnp.where(requeue, now + retry_after_ms, defer_until)
        n_throttles = n_throttles + requeue.astype(jnp.int32)
        p = state.fleet.inflight.shape[0]
        ep_oh = ep[None, :] == jnp.arange(p, dtype=jnp.int32)[:, None]
        n_requeue_ep = (ep_oh & requeue[None, :]).sum(axis=1).astype(
            jnp.int32)

    landed = (status0 == INFLIGHT) & (finish_ms <= now)
    # hard provider/application timeout: a request whose end-to-end latency
    # blew past timeout_mult x its deadline budget is a *failure*, not a
    # completion — this is the implicit failure mode (paper §2) that
    # explicit overload shedding exists to replace.
    e2e = finish_ms - batch.arrival_ms
    timed_out = landed & (
        e2e > cfg.timeout_mult[batch.bucket] * batch.deadline_budget_ms)
    done_now = landed & ~timed_out
    status = jnp.where(done_now, COMPLETED, jnp.where(timed_out, ABANDONED, status0))

    # tail signal: observed end-to-end latency vs unloaded expectation
    ratio_sum, k = _completed_ratio_sum(
        phys, done_now, finish_ms, batch.arrival_ms, batch.true_tokens)
    # divide by the SAMPLE size: past the cap ratio_sum covers only the
    # first EMA_SAMPLE_CAP completions, and dividing by the full k would
    # bias the tail signal toward 0 (the drain tick routinely lands
    # hundreds of completions at once)
    k_sample = jnp.minimum(k, EMA_SAMPLE_CAP)
    mean_ratio = jnp.where(k > 0, ratio_sum / jnp.maximum(k_sample, 1), 0.0)
    # the barrier pins the EMA's scalar rounding: without it XLA is free
    # to contract the mul+add into an FMA in one compilation and not the
    # other (the windowed and dense engines compile differently-shaped
    # programs around this identical scalar subgraph), and a 1-ulp EMA
    # drift eventually shifts severity — breaking the bit-exact contract
    delta = pinned(EMA_ALPHA * (mean_ratio - state.sched.ema_latency_ratio))
    ema = jnp.where(
        k > 0,
        state.sched.ema_latency_ratio + delta,
        state.sched.ema_latency_ratio,
    )

    # implicit client abandonment of stale pending work
    waited = now - batch.arrival_ms
    stale = (
        (status == PENDING)
        & (batch.arrival_ms <= now)
        & (waited > cfg.timeout_mult[batch.bucket] * batch.deadline_budget_ms)
    )
    status = jnp.where(stale, ABANDONED, status)

    inflight = (status == INFLIGHT).sum().astype(jnp.int32)
    inflight_tokens = jnp.where(status == INFLIGHT, batch.p50, 0.0).sum()

    fleet = state.fleet
    if fleet is not None:
        # per-endpoint recount: every INFLIGHT request carries its
        # endpoint, so the split is an exact one-hot masked sum — the
        # same recount-over-status discipline as the global counters
        # (and like them, exact in the windowed engine because every
        # INFLIGHT request lives in the window)
        p = fleet.inflight.shape[0]
        ep_oh = req.endpoint[None, :] == jnp.arange(p, dtype=jnp.int32)[:, None]
        live = ep_oh & (status == INFLIGHT)[None, :]
        fleet = fleet._replace(
            inflight=live.sum(axis=1).astype(jnp.int32),
            inflight_tokens=jnp.where(live, batch.p50[None, :], 0.0).sum(
                axis=1),
        )
        if n_requeue_ep is not None:
            fleet = fleet._replace(
                n_requeued=fleet.n_requeued + n_requeue_ep)

    return state._replace(
        req=req._replace(
            status=status,
            finish_ms=finish_ms,
            defer_until=defer_until,
            n_throttles=n_throttles,
        ),
        sched=state.sched._replace(
            ema_latency_ratio=ema,
            n_completed_obs=state.sched.n_completed_obs
            + k.astype(jnp.int32),
        ),
        provider=state.provider._replace(
            inflight=inflight, inflight_tokens=inflight_tokens
        ),
        fleet=fleet,
    )


@stages.scoped(stages.APPLY)
def _apply_batch(
    cfg: PolicyConfig,
    phys: ProviderPhysics,
    batch: RequestBatch,
    jitter: jnp.ndarray,
    state: SimState,
    d: BatchDecision,
    comfort_scale=None,
    limiter: ProviderDynamics | None = None,
    fleet: Fleet | None = None,
) -> SimState:
    """State transition for up to B grants, as one set of scatters.

    Grants target distinct requests by construction (each consumes a
    distinct entry of the ranked candidate lists), so the scatters never
    collide; idle rows are routed to the out-of-range index N and
    dropped.

    `comfort_scale` is this tick's brownout value (None = stationary);
    `limiter` enables the provider-boundary token bucket: an ADMIT whose
    class bucket is out of grants bounces 429-style — the request stays
    PENDING with `defer_until = now + retry_after` (the client-visible
    retry) and the DRR charge is refunded like any blocked release.
    Grants later in the same batch were decided against the optimistic
    inflight count (the client only observes the bounce after the send),
    which matches a real async client racing its own rate limit.

    `fleet` (mutually exclusive with `limiter`) switches to the (P,)
    provider axis: each grant lands on its `d.provider_idx` endpoint —
    service physics gather that endpoint's curve at *its* outstanding
    count, the rate limiter becomes the (P, K) per-endpoint bucket grid
    (rank arithmetic over the flattened P*K keys), and the request
    records its endpoint for the failover requeue.  At P == 1 the
    gathers collapse to endpoint 0 and the arithmetic is the exact
    single-provider program (the fleet P=1 bit-exactness contract).
    """
    n = batch.n
    req = state.req
    admit = d.actions == olc.ADMIT
    defer = d.actions == olc.DEFER
    reject = d.actions == olc.REJECT
    idx = d.req_idx
    deficit = d.deficit

    if limiter is not None:
        k = state.provider.tb_tokens.shape[0]
        gcls = jnp.clip(batch.cls[idx], 0, k - 1)
        # g-th grant's rank among this batch's admits of the same class:
        # admit is allowed iff the bucket holds that many grants
        take = (gcls[:, None] == jnp.arange(k, dtype=jnp.int32)) & admit[:, None]
        rank = (jnp.cumsum(take, axis=0) * take).sum(axis=-1)  # (B,) 1-based
        allowed = rank.astype(jnp.float32) <= state.provider.tb_tokens[gcls] + 1e-6
        throttled = admit & ~allowed
        admit = admit & allowed

    fl_limited = False
    if fleet is not None:
        p = fleet.phys.base_ms.shape[0]
        ep = jnp.clip(d.provider_idx, 0, p - 1)
        # optimistic admits (pre-bounce): the per-endpoint service load
        # mirrors d.inflight_at's optimism — the client only observes a
        # 429 after the send
        admit0 = admit
        if fleet.dyn is not None and fleet.dyn.tb_refill is not None:
            fl_limited = True
            k = state.fleet.tb_tokens.shape[1]
            gcls = jnp.clip(batch.cls[idx], 0, k - 1)
            # same rank-vs-bucket rule as the single-provider limiter,
            # over the flattened (P*K,) bucket keys
            key = ep * k + gcls
            take = (key[:, None] == jnp.arange(p * k, dtype=jnp.int32)) \
                & admit[:, None]
            rank = (jnp.cumsum(take, axis=0) * take).sum(axis=-1)
            allowed = rank.astype(jnp.float32) <= \
                state.fleet.tb_tokens.reshape(p * k)[key] + 1e-6
            throttled = admit & ~allowed
            admit = admit & allowed

    # per-grant service physics at the inflight level the grant saw —
    # identical floats to the sequential one-admit-at-a-time path.
    # NOTE: XLA:CPU contracts the trailing `service * jitter + now` into
    # an FMA here (a barrier does not stop LLVM-level contraction inside
    # one fusion); the live client's MockProvider reproduces that
    # rounding explicitly (repro.client.provider._fma32) to keep
    # session-vs-engine finish floats bit-identical.
    if fleet is None:
        service = service_time_ms(
            phys, batch.true_tokens[idx], d.inflight_at, jitter[idx],
            comfort_scale
        )
    elif p == 1:
        # endpoint 0 scalar gathers: () leaves and the global inflight,
        # exactly the single-provider arithmetic
        phys_g = ProviderPhysics(*(a[0] for a in fleet.phys))
        comfort_g = None if comfort_scale is None else \
            jnp.asarray(comfort_scale, jnp.float32)[0]
        service = service_time_ms(
            phys_g, batch.true_tokens[idx], d.inflight_at, jitter[idx],
            comfort_g
        )
    else:
        # (B,)-leaf physics gathered per grant; the load each grant sees
        # is its endpoint's outstanding count plus the same-endpoint
        # admits granted earlier in this batch (exclusive cumsum)
        phys_g = ProviderPhysics(*(a[ep] for a in fleet.phys))
        ep_oh = jax.nn.one_hot(ep, p, dtype=jnp.int32) * admit0[:, None]
        prior = jnp.cumsum(ep_oh, axis=0) - ep_oh
        infl_ep = state.fleet.inflight[ep] + (
            prior * jax.nn.one_hot(ep, p, dtype=jnp.int32)).sum(axis=1)
        comfort_g = None if comfort_scale is None else \
            jnp.asarray(comfort_scale, jnp.float32)[ep]
        service = service_time_ms(
            phys_g, batch.true_tokens[idx], infl_ep, jitter[idx], comfort_g
        )
    finish = state.now_ms + service
    backoff = olc.defer_backoff(cfg, d.severity, req.n_defers[idx])

    drop = jnp.int32(n)  # out-of-range => mode="drop" makes the row a no-op
    adm_i = jnp.where(admit, idx, drop)
    def_i = jnp.where(defer, idx, drop)
    rej_i = jnp.where(reject, idx, drop)

    status = req.status.at[adm_i].set(INFLIGHT, mode="drop")
    status = status.at[rej_i].set(REJECTED, mode="drop")
    submit = req.submit_ms.at[adm_i].set(state.now_ms, mode="drop")
    finish_ms = req.finish_ms.at[adm_i].set(finish, mode="drop")
    defer_until = req.defer_until.at[def_i].set(
        state.now_ms + backoff, mode="drop")
    n_defers = req.n_defers.at[def_i].add(1, mode="drop")
    n_throttles = req.n_throttles

    provider = state.provider
    if limiter is not None:
        thr_i = jnp.where(throttled, idx, drop)
        defer_until = defer_until.at[thr_i].set(
            state.now_ms + limiter.retry_after_ms, mode="drop")
        n_throttles = n_throttles.at[thr_i].add(1, mode="drop")
        consumed = (take & admit[:, None]).sum(axis=0).astype(jnp.float32)
        provider = provider._replace(
            tb_tokens=provider.tb_tokens - consumed,
            n_throttled=provider.n_throttled
            + throttled.sum().astype(jnp.int32),
        )
        # deficit conservation: the allocation layer charged for these
        # sends inside schedule_batch; the 429 blocked the release, so
        # credit it back exactly like a defer/reject refund (ADRR only).
        refund = (
            jax.nn.one_hot(gcls, k)
            * batch.p50[idx][:, None]
            * throttled[:, None]
        ).sum(axis=0) * (cfg.alloc_mode == ALLOC_ADRR)
        deficit = jnp.where(jnp.isfinite(deficit + refund),
                            deficit + refund, deficit)

    fstate = state.fleet
    endpoint = req.endpoint
    if fleet is not None:
        # record where each admit went (the failover requeue and the
        # per-endpoint recount both read this) and split the aggregate
        # updates along the endpoint axis
        endpoint = endpoint.at[adm_i].set(ep, mode="drop")
        adm_oh = jax.nn.one_hot(ep, p, dtype=jnp.int32) * admit[:, None]
        fstate = fstate._replace(
            inflight=fstate.inflight + adm_oh.sum(axis=0).astype(jnp.int32),
            inflight_tokens=fstate.inflight_tokens
            + (adm_oh.astype(jnp.float32) * batch.p50[idx][:, None]).sum(
                axis=0),
        )
        if fl_limited:
            thr_i = jnp.where(throttled, idx, drop)
            defer_until = defer_until.at[thr_i].set(
                state.now_ms + fleet.dyn.retry_after_ms, mode="drop")
            n_throttles = n_throttles.at[thr_i].add(1, mode="drop")
            consumed = (take & admit[:, None]).sum(axis=0).astype(
                jnp.float32).reshape(p, k)
            thr_oh = jax.nn.one_hot(ep, p, dtype=jnp.int32) \
                * throttled[:, None]
            fstate = fstate._replace(
                tb_tokens=fstate.tb_tokens - consumed,
                n_throttled=fstate.n_throttled
                + thr_oh.sum(axis=0).astype(jnp.int32),
            )
            # deficit conservation — same refund as the single-provider
            # limiter: the 429 blocked a charged release (ADRR only)
            refund = (
                jax.nn.one_hot(gcls, k)
                * batch.p50[idx][:, None]
                * throttled[:, None]
            ).sum(axis=0) * (cfg.alloc_mode == ALLOC_ADRR)
            deficit = jnp.where(jnp.isfinite(deficit + refund),
                                deficit + refund, deficit)
            provider = provider._replace(
                n_throttled=provider.n_throttled
                + throttled.sum().astype(jnp.int32),
            )

    inflight = provider.inflight + admit.sum().astype(jnp.int32)
    inflight_tokens = provider.inflight_tokens + jnp.where(
        admit, batch.p50[idx], 0.0
    ).sum()

    return state._replace(
        req=req._replace(
            status=status,
            submit_ms=submit,
            finish_ms=finish_ms,
            defer_until=defer_until,
            n_defers=n_defers,
            n_throttles=n_throttles,
            endpoint=endpoint,
        ),
        sched=state.sched._replace(deficit=deficit, rr_turn=d.rr_turn),
        provider=provider._replace(
            inflight=inflight, inflight_tokens=inflight_tokens
        ),
        fleet=fstate,
    )


def pack_batch(batch: RequestBatch) -> jnp.ndarray:
    """The batch's static fields as one `(8, N)` int32 table, one row per
    field in `RequestBatch` order: floats and ints bitcast, `valid` as
    0/1.  A gather of its columns moves the fields' bits unchanged, so a
    view built from the table is bit-identical to one gathered field by
    field.  Field-major, the gathered `(8, W)` block is lane-dense on the
    TPU and each field is a row of it; an `(N, 8)` table left `(W, 1)`
    pieces that cost a relayout each."""
    return jnp.stack(
        [f.astype(jnp.int32) if f.dtype == jnp.bool_
         else jax.lax.bitcast_convert_type(f, jnp.int32) for f in batch])


def _window_view(
    batch: RequestBatch, req: RequestState, slot_req: jnp.ndarray,
    batch_table: jnp.ndarray | None = None,
) -> tuple[RequestBatch, RequestState, jnp.ndarray]:
    """Gather the window's (W,)-shaped view of the batch and request
    state.  Empty slots (sentinel id n) clamp their gathers to a real
    row but are neutralized: valid=False (never eligible), terminal
    status (never counted live), finish=inf (never landing).  The batch
    fields come from one gather of `batch_table` (`pack_batch`, packed
    here when not given); the request state changes every tick, so its
    fields keep a gather each.  Returns (win_batch, win_req, occupied)."""
    n = batch.n
    occ = slot_req < n
    safe = jnp.minimum(slot_req, n - 1)
    if batch_table is None:
        batch_table = pack_batch(batch)
    cols = batch_table[:, safe]
    win_batch = RequestBatch(*(
        cols[j] != 0 if f.dtype == jnp.bool_
        else jax.lax.bitcast_convert_type(cols[j], f.dtype)
        for j, f in enumerate(batch)))
    win_batch = win_batch._replace(valid=win_batch.valid & occ)
    win_req = RequestState(
        status=jnp.where(occ, req.status[safe], jnp.int32(REJECTED)),
        submit_ms=req.submit_ms[safe],
        finish_ms=jnp.where(occ, req.finish_ms[safe], jnp.inf),
        defer_until=req.defer_until[safe],
        n_defers=req.n_defers[safe],
        n_throttles=req.n_throttles[safe],
        endpoint=None if req.endpoint is None else req.endpoint[safe],
    )
    return win_batch, win_req, occ


@stages.scoped(stages.RETIRE)
def _retire_window(
    cfg: PolicyConfig,
    phys: ProviderPhysics,
    batch: RequestBatch,
    state: SimState,
    win: WindowCarry,
    avail_t=None,
    retry_after_ms=None,
    batch_table: jnp.ndarray | None = None,
) -> tuple[SimState, jnp.ndarray]:
    """Windowed completion/timeout/stale pass: run the *dense* transition
    on the (W,) window view — one code path, so the formulas cannot
    drift — then scatter the updated statuses into the dense result
    arrays.  The EMA update inside is bit-exact with the dense engine
    because `_completed_ratio_sum` reduces a canonical fixed-width
    buffer in request-id order (the window's compaction invariant).
    Returns (state, alive) where alive marks slots still live (PENDING
    or INFLIGHT) after retirement.  `batch_table` is the batch's packed
    table (`pack_batch`), packed here when not given."""
    n = batch.n
    win_batch, win_req, occ = _window_view(batch, state.req, win.slot_req,
                                           batch_table)
    win_state = state._replace(req=win_req)
    win_state = _complete_and_timeout(cfg, phys, win_batch, win_state,
                                      avail_t=avail_t,
                                      retry_after_ms=retry_after_ms)
    status_w = win_state.req.status
    sidx = jnp.where(occ, win.slot_req, n)
    req = state.req
    if avail_t is not None:
        # the failover requeue rewrote more than status: scatter the
        # reset finish/backoff/throttle fields into the dense arrays too
        req = req._replace(
            finish_ms=req.finish_ms.at[sidx].set(
                win_state.req.finish_ms, mode="drop"),
            defer_until=req.defer_until.at[sidx].set(
                win_state.req.defer_until, mode="drop"),
            n_throttles=req.n_throttles.at[sidx].set(
                win_state.req.n_throttles, mode="drop"),
        )
    status = req.status.at[sidx].set(status_w, mode="drop")
    state = state._replace(
        req=req._replace(status=status),
        sched=win_state.sched,
        # inflight is an exact recount (every INFLIGHT request lives in
        # the window); inflight_tokens is a diagnostics-only float whose
        # reduction width differs from the dense engine's (not pinned)
        provider=win_state.provider,
        fleet=win_state.fleet,
    )
    alive = occ & ((status_w == PENDING) | (status_w == INFLIGHT))
    return state, alive


@stages.scoped(stages.ADMIT)
def _compact_and_admit(
    batch: RequestBatch, win: WindowCarry, alive: jnp.ndarray, now
) -> WindowCarry:
    """Reclaim retired slots and admit newly-arrived requests.

    Reclamation is a stable compaction (cumsum scatter): survivors keep
    their relative order, so the window stays sorted by request id and
    the free region is the tail.  Admission pops the arrival-sorted
    stream — `searchsorted` finds how many requests have arrived by
    `now` in O(log N), and the first `free` of the not-yet-admitted
    prefix append behind the survivors.  When the live queue exceeds W
    the overflow waits (FIFO by arrival) — correct but no longer
    bit-exact with the dense engine, which has no admission gate."""
    n = batch.n
    w = win.slot_req.shape[0]
    iota = jnp.arange(w, dtype=jnp.int32)
    pos = jnp.cumsum(alive.astype(jnp.int32)) - 1
    target = jnp.where(alive, pos, w)
    slot_req = jnp.full((w,), n, jnp.int32).at[target].set(
        win.slot_req, mode="drop")
    n_live = alive.sum().astype(jnp.int32)

    n_arrived = jnp.searchsorted(
        batch.arrival_ms, now, side="right").astype(jnp.int32)
    avail = jnp.maximum(n_arrived - win.arr_ptr, 0)
    n_admit = jnp.minimum(avail, w - n_live)
    new_req = win.arr_ptr + iota - n_live
    admit_here = (iota >= n_live) & (iota < n_live + n_admit)
    slot_req = jnp.where(admit_here, new_req, slot_req)
    return WindowCarry(
        slot_req=slot_req,
        arr_ptr=win.arr_ptr + n_admit,
        n_live=n_live + n_admit,
    )


def sim_tick(
    policy: PolicyConfig,
    phys: ProviderPhysics,
    batch: RequestBatch,
    jitter: jnp.ndarray,
    state: SimState,
    win: WindowCarry | None,
    xs: tuple,
    *,
    dt_ms: float,
    k_slots: int,
    backend: str,
    dynamics: ProviderDynamics | None = None,
    fleet: Fleet | None = None,
    collect_decisions: bool = False,
    batch_table: jnp.ndarray | None = None,
):
    """One decision epoch of the engine as a single traceable body:

      retire -> compact + admit -> limiter refill -> route -> dispatch
      -> apply

    This is THE per-tick program — `run_sim` scans it, and the live
    `ClientSession` fused tick is its transport-boundary sibling
    (retire/compact/dispatch are the same functions there; apply is
    split across the provider round-trip).  Module-level and explicit
    so the two paths share one definition of the tick, not two copies
    that drift.  `win=None` runs the dense O(N) transition; a
    `WindowCarry` runs the O(W) active-window path.  `fleet` switches
    every stage to the (P,) provider axis: the retire pass requeues
    in-flight work on down endpoints, the refill feeds the (P, K)
    bucket grid, and `routing.route_requests` fixes each request's
    endpoint (and route score term) before dispatch.  At the static
    P == 1 the route term is absent and the tick is the exact
    single-provider program.  `batch_table` is the batch's packed
    table (`pack_batch`) for the window views; `run_sim` packs it once
    per call, outside the scan.  Returns (state, win, ys) with ys the
    per-tick decision trace row (or None).
    """
    windowed = win is not None
    has_limiter = dynamics is not None and dynamics.tb_refill is not None
    fl_dyn = fleet.dyn if fleet is not None else None
    has_fleet_limiter = fl_dyn is not None and fl_dyn.tb_refill is not None
    t_idx, comfort_t, refill_t, avail_t = xs
    retry_ms = fl_dyn.retry_after_ms if avail_t is not None else None
    now = (t_idx + 1).astype(jnp.float32) * dt_ms
    state = state._replace(now_ms=now)
    if windowed:
        state, alive = _retire_window(policy, phys, batch, state, win,
                                      avail_t=avail_t,
                                      retry_after_ms=retry_ms,
                                      batch_table=batch_table)
        win = _compact_and_admit(batch, win, alive, now)
    else:
        state = _complete_and_timeout(policy, phys, batch, state,
                                      avail_t=avail_t,
                                      retry_after_ms=retry_ms)
    if has_limiter:
        state = state._replace(
            provider=state.provider._replace(
                tb_tokens=jnp.minimum(
                    state.provider.tb_tokens + refill_t,
                    dynamics.tb_capacity,
                )
            )
        )
    if has_fleet_limiter:
        state = state._replace(
            fleet=state.fleet._replace(
                tb_tokens=jnp.minimum(
                    state.fleet.tb_tokens + refill_t,
                    fl_dyn.tb_capacity,
                )
            )
        )
    if windowed:
        # the admitted window's view is the admission stage's output
        with jax.named_scope(stages.ADMIT):
            win_batch, win_req, _ = _window_view(batch, state.req,
                                                 win.slot_req, batch_table)
        d_batch, d_state = win_batch, state._replace(req=win_req)
    else:
        d_batch, d_state = batch, state
    route = endpoint = None
    if fleet is not None:
        p = fleet.phys.base_ms.shape[0]
        if p > 1:
            with jax.named_scope(stages.ROUTE):
                endpoint, route = route_requests(
                    fleet.phys, state.fleet, d_batch.p50,
                    comfort_t=comfort_t, avail_t=avail_t,
                    retry_after_ms=fl_dyn.retry_after_ms
                    if has_fleet_limiter else None,
                )
        else:
            # static P == 1: no routing choice exists — endpoint is an
            # integer constant and route stays None, so the scored
            # ordering program is exactly the single-provider one
            endpoint = jnp.zeros((d_batch.p50.shape[0],), jnp.int32)
    d = schedule_batch(
        policy, d_batch, d_state,
        max_grants=k_slots,
        backend=backend,
        route=route,
        endpoint=endpoint,
    )
    if windowed:
        # slot-local decision -> global request ids; empty slots
        # translate to the out-of-range n and fall into the scatter
        # drop path (IDLE rows never carry a release anyway).
        # d.provider_idx is already endpoint-valued — no translation.
        w = win.slot_req.shape[0]
        with jax.named_scope(stages.APPLY):
            d = d._replace(
                req_idx=win.slot_req[jnp.clip(d.req_idx, 0, w - 1)])
    state = _apply_batch(
        policy, phys, batch, jitter, state, d,
        comfort_scale=comfort_t,
        limiter=dynamics if has_limiter else None,
        fleet=fleet,
    )
    ys = (d.actions, d.req_idx, d.severity) if collect_decisions else None
    return state, win, ys


def run_sim(
    policy: PolicyConfig,
    batch: RequestBatch,
    jitter: jnp.ndarray,
    phys: ProviderPhysics,
    sim_cfg: SimConfig = SimConfig(),
    dynamics: ProviderDynamics | None = None,
    collect_decisions: bool = False,
    fleet: Fleet | None = None,
) -> SimState | tuple[SimState, tuple]:
    """Run the full horizon; returns the final SimState (jit-friendly).

    `dynamics` threads time-varying provider schedules through the scan
    as (T,)-shaped xs (DESIGN.md §5).  Which mechanisms exist is pytree
    structure — `dynamics=None` (or all-None fields) traces exactly the
    stationary program, and schedule *content* never changes trace size:
    scenario complexity is O(1) at compile time.

    `sim_cfg.window = W` switches the scan to the active-window engine
    (DESIGN.md §6): per-tick cost O(W) instead of O(N·K), bit-exact with
    the dense path whenever W covers the peak live queue.  Windowed mode
    requires `batch.arrival_ms` sorted ascending (the workload
    generator's native order).

    `collect_decisions=True` (static) additionally returns the per-tick
    decision trace `(actions (T,B), req_idx (T,B), severity (T,))` with
    req_idx in *global* request ids on both engines — the hook the
    per-decision bit-exactness pins compare.

    `fleet` (mutually exclusive with `dynamics`) switches to the (P,)
    provider axis (DESIGN.md §10): per-endpoint physics/schedules drive
    service and failover, `routing.route_requests` fixes each request's
    endpoint before dispatch, and `SimState.fleet` carries the
    per-endpoint split.  `phys` remains the *reference* physics the
    tail-EMA expectation is computed against (one canonical
    expectation, independent of which endpoint served the request).
    With P == 1 and no fleet dynamics the decision sequence is
    bit-exact with the single-provider engine.
    """
    n = batch.n
    if fleet is not None and dynamics is not None:
        raise ValueError(
            "fleet and dynamics are mutually exclusive: use "
            "FleetDynamics for per-endpoint schedules")
    windowed = sim_cfg.window is not None
    state0 = init_sim_state(n, n_classes(policy))
    has_brownout = dynamics is not None and dynamics.comfort_scale is not None
    has_limiter = dynamics is not None and dynamics.tb_refill is not None
    if has_limiter:
        # buckets start full: the burst capacity is available at t=0
        state0 = state0._replace(
            provider=state0.provider._replace(tb_tokens=dynamics.tb_capacity)
        )
    fl_dyn = fleet.dyn if fleet is not None else None
    has_fleet_limiter = fl_dyn is not None and fl_dyn.tb_refill is not None
    if fleet is not None:
        p = fleet.phys.base_ms.shape[0]
        fstate0 = init_fleet_state(p, n_classes(policy))
        if has_fleet_limiter:
            fstate0 = fstate0._replace(tb_tokens=fl_dyn.tb_capacity)
        state0 = state0._replace(
            req=state0.req._replace(endpoint=jnp.zeros((n,), jnp.int32)),
            fleet=fstate0,
        )

    def tick(carry, xs):
        state, win = carry
        state, win, ys = sim_tick(
            policy, phys, batch, jitter, state, win, xs,
            dt_ms=sim_cfg.dt_ms,
            k_slots=sim_cfg.k_slots,
            backend=sim_cfg.ordering_backend,
            dynamics=dynamics,
            fleet=fleet,
            collect_decisions=collect_decisions,
            batch_table=batch_table,
        )
        return (state, win), ys

    # the batch never changes inside the scan: pack it once a call
    batch_table = pack_batch(batch) if windowed else None
    win0 = init_window_carry(sim_cfg.window, n) if windowed else None
    xs = (
        jnp.arange(sim_cfg.n_ticks),
        fl_dyn.comfort_scale if fl_dyn is not None
        else (dynamics.comfort_scale if has_brownout else None),
        fl_dyn.tb_refill if has_fleet_limiter
        else (dynamics.tb_refill if has_limiter else None),
        fl_dyn.avail if fl_dyn is not None else None,
    )
    (final, win), trace = jax.lax.scan(tick, (state0, win0), xs)
    # drain bookkeeping: completions that land exactly at/after the horizon
    final = final._replace(now_ms=final.now_ms + 1e9)
    if windowed:
        # retire through the window first (completions land here; the
        # canonical EMA sample stays bit-exact with the dense drain),
        # then run the full dense transition once: after _retire_window
        # nothing anywhere is INFLIGHT, so it reduces to exactly the
        # stale-abandonment pass — reaching requests the window never
        # admitted (arrived past the horizon, or overflow still queued)
        # with the one and only definition of the timeout rule.  O(N),
        # but once per run, not per tick.
        final, _ = _retire_window(policy, phys, batch, final, win,
                                  batch_table=batch_table)
    final = _complete_and_timeout(policy, phys, batch, final)
    if collect_decisions:
        return final, trace
    return final
