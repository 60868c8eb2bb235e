"""The fused three-layer client scheduler (paper §3), K-class generalized.

`schedule_slot` composes the layers exactly as the paper describes:
the allocation layer selects a class; the ordering layer names a concrete
request in that class; the overload layer may block or delay that release.
It is a pure function of (PolicyConfig, RequestBatch, SimState) and
returns a `SlotDecision`.

`schedule_batch` is the multi-grant generalization (DESIGN.md §3): one
vectorized pass that grants up to B releases per decision epoch.  The
O(K·N) work — eligibility, the per-class ranked candidate lists, the
severity evaluation — happens up front, outside the grant loop; only
the O(K) allocation step runs per grant, so a tick costs O(K·N + B·K)
instead of the B full `schedule_slot` traces the sequential slot loop
paid.  Severity is
frozen across the B grants (one cost-ladder evaluation drives every
admission decision in the batch), while DRR deficits, per-class and
global inflight caps, and the FQ pointer update cumulatively per grant.
With max_grants=1 the pass reduces bit-exactly to `schedule_slot`.

Both entry points are consumed by the simulation engine
(repro.sim.engine) and the live serving adapter (repro.serving.blackbox),
so the policy logic is written once.

Fleet dispatch (DESIGN.md §10) slots in *above* these layers: when a
`(N,)` endpoint assignment and `(N,)` route-cost vector are provided
(from `core.routing.route_requests`), the route cost joins the ordering
score as a fourth term and `schedule_batch` gathers the chosen
endpoint into `BatchDecision.provider_idx` per grant — which-request
and which-endpoint stay separable decisions, and with `endpoint=None`
the compiled program is the single-provider one unchanged.

The class count K is static — the length of `PolicyConfig`'s per-class
arrays and of `SchedState.deficit`.  All per-class computation here is
vectorized over a (K, N) class-membership mask (no Python loop over
classes), so trace size and compile time are O(1) in K and the same
compiled program shape serves the paper's 2-lane split, a per-bucket
4-lane scheme, or K tenants.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import drr, ordering, overload, stages
from repro.core.policy import ALLOC_ADRR, PolicyConfig, n_classes
from repro.core.types import INFLIGHT, RequestBatch, SimState


class SlotDecision(NamedTuple):
    action: jnp.ndarray       # () int32: -1 idle, 0 admit, 1 defer, 2 reject
    req_idx: jnp.ndarray      # () int32 target request (valid iff action>=0)
    severity: jnp.ndarray     # () f32 overload severity used
    deficit: jnp.ndarray      # (K,) f32 updated allocation deficits
    rr_turn: jnp.ndarray      # () int32 updated FQ pointer


class BatchDecision(NamedTuple):
    """Up to B grants from one vectorized dispatch pass.

    Row g is the g-th grant in decision order; rows with action == IDLE
    carry no release (their req_idx must be ignored).  `inflight_at` is
    the provider inflight count each grant was decided against, so
    consumers can reproduce the sequential engine's per-admit service
    physics exactly.
    """

    actions: jnp.ndarray      # (B,) int32: -1 idle, 0 admit, 1 defer, 2 reject
    req_idx: jnp.ndarray      # (B,) int32 target request (valid iff action>=0)
    inflight_at: jnp.ndarray  # (B,) int32 inflight total seen by grant g
    severity: jnp.ndarray     # () f32 severity shared by all B decisions
    deficit: jnp.ndarray      # (K,) f32 updated allocation deficits
    rr_turn: jnp.ndarray      # () int32 updated FQ pointer
    # (B,) int32 fleet endpoint per grant (fleet mode only; None in
    # single-provider mode — the absence is pytree structure, so the
    # P=1-free program is byte-identical to the pre-fleet one)
    provider_idx: Optional[jnp.ndarray] = None


IDLE = -1


def effective_class(cfg: PolicyConfig, batch: RequestBatch) -> jnp.ndarray:
    """Info-ladder: without class routing every request shares one lane.

    Class ids are clipped into [0, K) so a batch generated for a larger
    class scheme degrades gracefully instead of indexing out of range.
    """
    k = n_classes(cfg)
    cls = jnp.clip(batch.cls, 0, k - 1)
    return jnp.where(cfg.route_by_class > 0, cls, 0).astype(jnp.int32)


def _refund(cfg, k, cls_id, head_cost, action, ignore_class):
    """Deficit conservation: DRR charged the head cost assuming a
    release; credit it back when the overload layer blocked the release
    (defer/reject consumed no share).  Only ADRR ever charges, so the
    refund is gated on the mode — FQ/quota/SP/naive deficits must not
    be silently credited across mode switches."""
    return (
        jax.nn.one_hot(cls_id, k)
        * head_cost[cls_id]
        * ((action == overload.DEFER) | (action == overload.REJECT))
        * (~ignore_class)
        * (cfg.alloc_mode == ALLOC_ADRR)
    )


def charge_resubmit(cfg: PolicyConfig, deficit: jnp.ndarray,
                    charge: jnp.ndarray) -> jnp.ndarray:
    """Debit resubmission traffic against the class deficits.

    The client's resilience layer re-sends stuck requests through the
    same provider boundary the scheduler meters — if that recovery
    traffic rode for free, a class with a high fault rate could starve
    the others through its retries.  `charge` is the (K,) per-class sum
    of p50 costs resubmitted this epoch; like `_refund`, the debit is
    gated on ADRR (the only mode that charges deficits at all) and on
    an actual charge being present, so the zero-charge epoch returns
    `deficit` bit-unchanged (x - 0.0 is not an f32 identity at -0.0)
    and the no-resilience trace never contains this op at all.
    """
    debited = deficit - charge
    return jnp.where(
        (charge > 0.0).any() & jnp.isfinite(debited).all()
        & (cfg.alloc_mode == ALLOC_ADRR),
        debited, deficit)


def schedule_slot(
    cfg: PolicyConfig, batch: RequestBatch, state: SimState
) -> SlotDecision:
    k = n_classes(cfg)
    now = state.now_ms
    elig = ordering.eligibility(
        batch, state.req.status, state.req.defer_until, now
    )
    eff_cls = effective_class(cfg, batch)

    # (K, N) class-membership masks — the vectorized class axis
    cls_onehot = eff_cls[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]
    elig_kn = cls_onehot & elig[None, :]

    # --- layer 2 first per class: the allocation layer needs each class's
    # would-be head cost to test deficit affordability (classic DRR).
    cand_idx, cand_ok = ordering.select_per_class(batch, elig_kn, now, cfg)
    head_cost = jnp.where(cand_ok, batch.p50[cand_idx], jnp.inf)

    backlog = elig_kn.sum(axis=1).astype(jnp.int32)

    inflight_mask = state.req.status == INFLIGHT
    inflight_cls = (cls_onehot & inflight_mask[None, :]).sum(axis=1).astype(
        jnp.int32
    )
    inflight_total = state.provider.inflight

    # --- layer 3 signals (client-observable only)
    sev = overload.severity_score(
        cfg,
        inflight_total=inflight_total,
        n_pending=elig.sum(),
        ema_latency_ratio=state.sched.ema_latency_ratio,
    )

    # --- layer 1: which class gets this send opportunity?
    choice = drr.allocate(
        cfg,
        backlog=backlog,
        head_cost=head_cost,
        inflight_cls=inflight_cls,
        inflight_total=inflight_total,
        severity=sev,
        deficit=state.sched.deficit,
        rr_turn=state.sched.rr_turn,
    )

    # naive mode ignores lanes entirely: global FIFO
    fifo_idx, fifo_ok = ordering.select_fifo(batch, elig)
    idx = jnp.where(choice.ignore_class, fifo_idx, cand_idx[choice.cls_id])
    ok = jnp.where(choice.ignore_class, fifo_ok, cand_ok[choice.cls_id])
    ok = ok & choice.send_ok

    # --- layer 3 decision on the concrete candidate
    act = overload.admission_action(
        cfg,
        severity=sev,
        bucket=batch.bucket[idx],
        n_defers=state.req.n_defers[idx],
    )
    action = jnp.where(ok, act, IDLE).astype(jnp.int32)

    refund = _refund(cfg, k, choice.cls_id, head_cost, action,
                     choice.ignore_class)
    deficit = jnp.where(
        jnp.isfinite(choice.deficit + refund), choice.deficit + refund, choice.deficit
    )

    return SlotDecision(
        action=action,
        req_idx=idx.astype(jnp.int32),
        severity=sev,
        deficit=deficit,
        rr_turn=choice.rr_turn,
    )


def schedule_batch(
    cfg: PolicyConfig,
    batch: RequestBatch,
    state: SimState,
    max_grants: int = 1,
    backend: str = "jnp",
    route=None,
    endpoint=None,
) -> BatchDecision:
    """Grant up to `max_grants` releases in one vectorized pass.

    The expensive O(K·N) layer-2 work runs up front, outside the grant
    loop: eligibility, the ranked top-B candidate list per class
    (`ordering.select_top_b` — one top_k pass on the jnp backend, K·B
    fused argmax streams on the Pallas backend), the global FIFO ranking
    for the naive lane, and one severity evaluation shared by every
    grant's cost-ladder decision.
    The per-grant loop then replays only the O(K) allocation step —
    deficits are charged per grant, per-class caps and the global
    max_inflight bind cumulatively (each admit raises the counts the
    next grant is decided against), and a deferred/rejected candidate
    leaves the feasible set for the rest of the batch exactly as its
    backoff/terminal status would remove it in the sequential path.

    `max_grants` and `backend` must be static under jit.  With
    max_grants=1 the decision stream is bit-exact with `schedule_slot`.

    Fleet mode (`route`/`endpoint` from `routing.route_requests`): the
    (N,) route term joins the scored ordering, and each grant's row in
    `BatchDecision.provider_idx` is the granted request's pre-computed
    best endpoint — routing happens above allocation, so the three
    paper layers are unchanged and a (P,)-aware consumer only has to
    gather.  Both default to None; passing neither reproduces the
    single-provider program exactly.
    """
    k = n_classes(cfg)
    bmax = min(int(max_grants), batch.n)
    now = state.now_ms
    with jax.named_scope(stages.ORDER):
        elig = ordering.eligibility(
            batch, state.req.status, state.req.defer_until, now
        )
        eff_cls = effective_class(cfg, batch)
        cls_onehot = (eff_cls[None, :]
                      == jnp.arange(k, dtype=jnp.int32)[:, None])
        elig_kn = cls_onehot & elig[None, :]

        # --- layer 2 once: ranked candidates per class + global FIFO lane
        rank_idx, n_elig_cls = ordering.select_top_b(
            batch, elig_kn, now, cfg, bmax, backend=backend, route=route
        )
        glob_idx, n_elig_tot = ordering.rank_fifo(batch, elig, bmax,
                                                  backend=backend)
        # grantable candidates this batch can actually see per lane
        visible_cls = jnp.minimum(n_elig_cls, bmax)
        visible_glob = jnp.minimum(n_elig_tot, bmax)

    with jax.named_scope(stages.GRANT):
        # the grant loop's starting per-class inflight counts
        inflight_mask = state.req.status == INFLIGHT
        inflight_cls0 = (cls_onehot & inflight_mask[None, :]).sum(
            axis=1).astype(jnp.int32)

    # --- layer 3 once: a single severity drives all B ladder decisions
    with jax.named_scope(stages.OVERLOAD):
        sev = overload.severity_score(
            cfg,
            inflight_total=state.provider.inflight,
            n_pending=n_elig_tot,
            ema_latency_ratio=state.sched.ema_latency_ratio,
        )

    def grant(g, carry):
        (deficit, rr_turn, infl_cls, infl_tot, cls_ptr, glob_ptr,
         actions, idxs, infl_at) = carry

        # per-class heads at the current rank pointers
        col = jnp.clip(cls_ptr, 0, bmax - 1)
        head_idx = rank_idx[jnp.arange(k), col]
        ok_c = cls_ptr < visible_cls
        head_cost = jnp.where(ok_c, batch.p50[head_idx], jnp.inf)
        backlog = (visible_cls - cls_ptr).astype(jnp.int32)

        choice = drr.allocate(
            cfg,
            backlog=backlog,
            head_cost=head_cost,
            inflight_cls=infl_cls,
            inflight_total=infl_tot,
            severity=sev,
            deficit=deficit,
            rr_turn=rr_turn,
        )

        gidx = glob_idx[jnp.clip(glob_ptr, 0, bmax - 1)]
        ok_g = glob_ptr < visible_glob
        idx = jnp.where(choice.ignore_class, gidx, head_idx[choice.cls_id])
        ok = jnp.where(choice.ignore_class, ok_g, ok_c[choice.cls_id])
        ok = ok & choice.send_ok

        act = overload.admission_action(
            cfg,
            severity=sev,
            bucket=batch.bucket[idx],
            n_defers=state.req.n_defers[idx],
        )
        action = jnp.where(ok, act, IDLE).astype(jnp.int32)

        refund = _refund(cfg, k, choice.cls_id, head_cost, action,
                         choice.ignore_class)
        deficit = jnp.where(
            jnp.isfinite(choice.deficit + refund),
            choice.deficit + refund,
            choice.deficit,
        )

        # cumulative bookkeeping for the next grant: any live decision
        # consumes its candidate (a deferred/rejected request is out of
        # the feasible set for the rest of the batch); only admits hold
        # provider slots.
        live = (action != IDLE).astype(jnp.int32)
        admit = (action == overload.ADMIT).astype(jnp.int32)
        gcls = eff_cls[idx]
        cls_take = jax.nn.one_hot(gcls, k, dtype=jnp.int32) * live
        use_glob = choice.ignore_class.astype(jnp.int32)
        return (
            deficit,
            choice.rr_turn,
            infl_cls + cls_take * admit,
            infl_tot + admit,
            cls_ptr + cls_take * (1 - use_glob),
            glob_ptr + live * use_glob,
            actions.at[g].set(action),
            idxs.at[g].set(idx.astype(jnp.int32)),
            infl_at.at[g].set(infl_tot),
        )

    with jax.named_scope(stages.GRANT):
        carry0 = (
            state.sched.deficit,
            state.sched.rr_turn,
            inflight_cls0,
            state.provider.inflight,
            jnp.zeros((k,), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.full((bmax,), IDLE, jnp.int32),
            jnp.zeros((bmax,), jnp.int32),
            jnp.zeros((bmax,), jnp.int32),
        )
        (deficit, rr_turn, _, _, _, _, actions, idxs,
         infl_at) = jax.lax.fori_loop(0, bmax, grant, carry0)
    provider_idx = None
    if endpoint is not None:
        # gather-only: the endpoint choice was fixed before allocation,
        # so granting never re-routes (integer gather, no float math)
        provider_idx = endpoint[jnp.clip(idxs, 0, batch.n - 1)].astype(
            jnp.int32)
    return BatchDecision(
        actions=actions,
        req_idx=idxs,
        inflight_at=infl_at,
        severity=sev,
        deficit=deficit,
        rr_turn=rr_turn,
        provider_idx=provider_idx,
    )
