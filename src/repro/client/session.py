"""`ClientSession` — the transport-agnostic streaming client API.

The paper's stack is a *client-side* scheduler at a black-box API
boundary, so the client surface is the product: requests arrive over
time (`submit`), the session makes batched admit/defer/reject decisions
(`poll`), and work flows through an `AsyncProvider` that may 429 it.
Unlike the old `ScheduledClient.run(requests)` — a closed upfront list,
dense O(N) state per poll, one blocking request in flight — the session
is open-ended and windowed:

  * **State is a compacted (W,) slot pool**, the live-client mirror of
    the sim engine's `WindowCarry` (DESIGN.md §6): every live request
    (admitted to the window, not yet terminal) holds one slot, occupied
    slots form a request-id-sorted prefix, and each poll's cost is
    O(W + B) regardless of how many requests the session has ever seen.
    Submissions beyond the window queue FIFO and admit as slots free.
  * **One device step per poll** (DESIGN.md §8): the whole decision
    epoch — apply the previous epoch's verdicts, ingest completions,
    retire, compact + admit, dispatch — is a single donated-buffer
    `jax.jit` (`_fused_tick`).  The slot pool never leaves the device:
    the host pushes one packed f32 vector (the completion scatter, the
    newly-staged arrivals, the epoch's scalars and the previous epoch's
    verdicts) and pulls one packed `(4B+2,)` decision summary.  Terminal
    classification (completed vs abandoned) runs on host-side float32
    mirrors that replay the device's own comparison chains bit-exactly,
    so the per-poll `(W,)` status pulls of the unfused design are gone.
  * **Decisions come from the same `schedule_batch`** the simulator
    runs, on the same `(K, W)` view; retirement (completion/timeout
    classification, the tail-latency EMA) is literally the engine's
    `_complete_and_timeout` on the (W,) state.  The policy logic and
    the decision-feeding float chains are written once, which is what
    makes sim↔live parity a theorem rather than a hope: driven in
    virtual time over `MockProvider`, the session reproduces the
    windowed sim engine's decision sequence bit-for-bit
    (tests/test_serving_client.py pins this on the `balanced` regime).
  * **The provider boundary is async**: submits are non-blocking, many
    requests ride in flight at once, and the session's concurrency
    accounting is the real INFLIGHT recount (== the provider's actual
    outstanding count), not a bracket around a blocking call.  A 429
    bounce parks the request until `now + retry_after` through the
    session's `retry_policy` hook — the place Retry-After-aware backoff
    strategies plug in (the `rate_crunch` regime is where they
    separate).  The boundary is one provider wide by contract:
    fleet-scale sessions hand the session a
    `repro.client.fleet.FleetProvider`, which multiplexes P child
    endpoints behind this same interface with endpoint-aware routing
    (DESIGN.md §10) — the session itself never learns P exists.
  * **Two clocks.**  `clock="virtual"` advances `dt_ms` per poll (or an
    explicit `now_ms`) — deterministic replays, tests, benchmarks.
    `clock="wall"` reads the monotonic clock scaled by `time_scale`,
    and `drain()` sleeps until the next actionable instant (next queued
    arrival, earliest defer/Retry-After expiry, the provider's next
    event hint) instead of spinning at a fixed cadence.

Decision timing under the fused step: `schedule_batch` runs at the end
of epoch t's device call, the host submits the grants and collects the
provider's 429 verdicts, and the state transition (`_apply_decisions`)
is the *first* stage of epoch t+1's call — the same floats in the same
order as applying at the end of t, since nothing between reads the
written fields.  Reading `session._state` flushes that pending
transition on demand, so introspection still sees post-apply state.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.client.provider import (
    AsyncProvider,
    Completion,
    expo_retry,  # noqa: F401  (re-exported; historic home of the hook)
    honor_retry_after,
    sanitize_retry_after_ms,
)
from repro.client.request import Request
from repro.client.resilience import ResilienceConfig, Watchdog
from repro.core import overload as olc
from repro.core import stages
from repro.core.policy import PolicyConfig, n_classes
from repro.core.scheduler import (
    IDLE,
    apply_verdicts,
    charge_resubmit,
    schedule_batch,
)
from repro.core.types import (
    INFLIGHT,
    PENDING,
    REJECTED,
    RequestBatch,
    SimState,
    empty_window_batch,
    empty_window_request_state,
    init_sim_state,
)
from repro.sim.engine import _complete_and_timeout
from repro.sim.provider import ProviderPhysics, default_physics
from repro.sim.workload import DEADLINE_BUDGET_MS

_DEADLINE_NP = np.asarray(DEADLINE_BUDGET_MS)
_DEADLINE_PY = [float(x) for x in _DEADLINE_NP]


# ---------------------------------------------------------------------------
# Configuration and result records
# ---------------------------------------------------------------------------


class SessionConfig(NamedTuple):
    window: int = 256          # slot-pool capacity W (per-poll cost is O(W))
    max_grants: int = 4        # batch dispatch width B per poll
    dt_ms: float = 25.0        # virtual tick / decision-epoch granularity
    backend: str = "jnp"       # ordering backend ("jnp" | "pallas")
    time_scale: float = 1.0    # wall mode: session ms per wall ms
    max_idle_sleep_ms: float = 250.0  # wall mode: cap on one idle sleep
                                      # (session clock ms)


class PollResult(NamedTuple):
    """One decision epoch's outcome (all rids are session-scoped)."""

    now_ms: float
    actions: np.ndarray        # (B,) int32 decision per grant row
    req_rids: np.ndarray       # (B,) session rid per grant row (-1 = idle)
    severity: np.float32       # overload severity this epoch's ladder used
    completed: list[int]
    abandoned: list[int]
    rejected: list[int]
    admitted: list[int]
    deferred: list[int]
    throttled: list[int]       # 429-bounced this epoch
    n_live: int                # occupied window slots after admission
    progressed: bool           # anything moved (else the caller may sleep)


@dataclasses.dataclass
class SessionStats:
    n_polls: int = 0
    n_admitted: int = 0
    n_completed: int = 0
    n_rejected: int = 0
    n_abandoned: int = 0
    n_deferred: int = 0
    n_throttled: int = 0
    n_idle_sleeps: int = 0
    peak_inflight: int = 0
    # resilience / dup-safety accounting (zero on honest transports)
    n_resubmitted: int = 0      # watchdog resubmissions accepted
    n_gave_up: int = 0          # budget exhausted -> synthetic abandon
    n_dup_discarded: int = 0    # dead-ticket / same-epoch dup arrivals
    n_late_discarded: int = 0   # completions for already-retired rids


RetryPolicy = Callable[[float, int], float]


# the phases of a profiled poll, in order, and the `enable_profiling()`
# bucket each adds to
_PHASE_BUCKET = {"ingest": "stage", "classify": "stage", "staging": "stage",
                 "dispatch": "dispatch", "mirrors": "stage", "pull": "pull",
                 "grants": "grants"}


class _PollSpans:
    """The phases of profiled polls.  Each phase is a profiler annotation
    `session.<phase>` (so a device trace shows it on the device ops'
    clock) and a `perf_counter` duration added to its bucket of `prof`.
    Phases are contiguous: `to(phase)` ends the open phase and starts the
    next, `end()` ends the last one.  The annotations are swapped back to
    back and the bookkeeping runs inside them, so a trace shows no gap
    between phases."""

    def __init__(self, prof: dict):
        self.prof = prof
        self._phase: Optional[str] = None
        self._start = 0.0
        self._annotation = None

    def to(self, phase: str) -> None:
        annotation = jax.profiler.TraceAnnotation("session." + phase)
        now = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        annotation.__enter__()
        self._book(now)
        self._phase, self._start, self._annotation = phase, now, annotation

    def end(self) -> None:
        self._book(time.perf_counter())
        self.prof["polls"] += 1
        self._annotation.__exit__(None, None, None)
        self._phase = self._annotation = None

    def _book(self, now: float) -> None:
        if self._phase is not None:
            self.prof[_PHASE_BUCKET[self._phase]] += now - self._start


# ---------------------------------------------------------------------------
# The fused device tick (module-level so compilations are shared)
# ---------------------------------------------------------------------------


# row layout of the (7, W) staging part of the per-poll transfer: int
# fields ride exactly in f32 (buckets/classes are tiny)
_ST_ARRIVAL, _ST_BUCKET, _ST_CLS, _ST_TOKENS = 0, 1, 2, 3
_ST_P50, _ST_P90, _ST_DEADLINE = 4, 5, 6


def _xfer_parts(w: int, b: int) -> tuple:
    """Where each per-poll input of the fused tick lies in the packed
    host-to-device transfer, one flat f32 vector:

      [comp (2, W) | staged (7, W) | n_stage | now | accepted (B) |
       delay_ms (B) | resub (K), on sessions with a watchdog only]

    Int fields ride exactly in f32 (`n_stage <= W < 2^24`).  Each host
    argument of a jitted call is a device put of its own, and a put
    costs per transfer, not per byte, so the session pushes this one
    buffer instead of an argument per field.  Returns the (comp,
    staged, n_stage, now, verdicts, resub) slices and indices."""
    v = 9 * w + 2
    return (slice(0, 2 * w), slice(2 * w, 9 * w), 9 * w, 9 * w + 1,
            slice(v, v + 2 * b), slice(v + 2 * b, None))


def _unpack_xfer(xfer, w: int, b: int) -> tuple:
    """The fused tick's view of the packed transfer (`_xfer_parts`):
    `(comp, staged, n_stage, now, verdicts, resub)`, with `resub` None
    where the buffer ends after the verdicts (no watchdog)."""
    comp, staged, n_stage, now, verdicts, resub = _xfer_parts(w, b)
    charge = xfer[resub]
    return (xfer[comp].reshape(2, w), xfer[staged].reshape(7, w),
            xfer[n_stage].astype(jnp.int32), xfer[now], xfer[verdicts],
            charge if charge.shape[0] else None)


@stages.scoped(stages.ADMIT)
def _compact_and_admit(batch: RequestBatch, req, alive, staged, n_stage):
    """Stable-compact live slots to the prefix (preserving request-id
    order — the ordering layer's tie-break invariant) and append up to
    `n_stage` newly admitted requests behind them (rows of the packed
    (7, W) staging transfer).  Staged request state is fresh (PENDING,
    finish=inf); vacated slots are neutralized exactly like the
    engine's empty-slot view (invalid, terminal, never landing)."""
    w = alive.shape[0]
    iota = jnp.arange(w, dtype=jnp.int32)
    idx, = jnp.nonzero(alive, size=w, fill_value=0)
    n_live = alive.sum().astype(jnp.int32)
    live_here = iota < n_live
    stage_here = (iota >= n_live) & (iota < n_live + n_stage)
    spos = jnp.clip(iota - n_live, 0, w - 1)

    def mix(old, st, fill=None):
        v = jnp.where(stage_here, st[spos], old[idx])
        if fill is not None:
            v = jnp.where(live_here | stage_here, v, fill)
        return v

    new_batch = RequestBatch(
        arrival_ms=mix(batch.arrival_ms, staged[_ST_ARRIVAL]),
        bucket=mix(batch.bucket, staged[_ST_BUCKET].astype(jnp.int32)),
        cls=mix(batch.cls, staged[_ST_CLS].astype(jnp.int32)),
        true_tokens=mix(batch.true_tokens, staged[_ST_TOKENS]),
        p50=mix(batch.p50, staged[_ST_P50]),
        p90=mix(batch.p90, staged[_ST_P90]),
        deadline_budget_ms=mix(batch.deadline_budget_ms,
                               staged[_ST_DEADLINE]),
        # every staged row is an admission, so validity needs no
        # transferred column
        valid=jnp.where(stage_here, True,
                        jnp.where(live_here, batch.valid[idx], False)),
    )
    fresh_i = jnp.zeros((w,), jnp.int32)
    fresh_f = jnp.zeros((w,), jnp.float32)
    inf_f = jnp.full((w,), jnp.inf, jnp.float32)
    new_req = req._replace(
        status=mix(req.status, fresh_i, fill=jnp.int32(REJECTED)),
        submit_ms=mix(req.submit_ms, inf_f),
        finish_ms=mix(req.finish_ms, inf_f, fill=jnp.inf),
        defer_until=mix(req.defer_until, fresh_f),
        n_defers=mix(req.n_defers, fresh_i),
        n_throttles=mix(req.n_throttles, fresh_i),
    )
    return new_batch, new_req, n_live + n_stage


@stages.scoped(stages.APPLY)
def _apply_body(policy: PolicyConfig, batch: RequestBatch,
                state: SimState, d, accepted, delay_ms):
    """Post-dispatch state transition on the (W,) pool: the shared
    `apply_verdicts`, with the verdicts the live boundary gives.  Admits
    get no finish time (the transport decides when work lands;
    completion arrives via the provider poll), and the bounce comes
    from the provider's actual submit responses (`accepted`), parked
    for the session retry policy's `delay_ms`, instead of an
    engine-owned token bucket."""
    admit = (d.actions == olc.ADMIT) & accepted
    throttled = (d.actions == olc.ADMIT) & ~accepted
    return apply_verdicts(policy, batch, state, d, admit, throttled,
                          retry_ms=delay_ms)


# standalone jit of the transition, used only when `session._state` is
# introspected before the next poll has folded the pending apply in.
# RPL002 audit: donates position 2 (the RequestState bundle); the sole
# caller (`_state`) rebinds `self._dev_state` from the result in the
# same statement, so no stale binding survives the call.
_apply_decisions = jax.jit(_apply_body, donate_argnums=(2,))


def _fused_tick(policy: PolicyConfig, phys: ProviderPhysics,
                batch: RequestBatch, state: SimState, prev, xfer,
                staged=None, n_stage=None, now=None,
                *, max_grants: int, backend: str):
    """One decision epoch as a single donated-buffer device step:

      apply(prev) -> charge resubmits -> ingest completions -> retire
                  -> compact + admit -> dispatch -> packed summary

    `prev` is the previous epoch's `BatchDecision` — or None on the
    first epoch / after an explicit `_state` flush, a distinct pytree
    structure that traces the no-leading-apply variant.  `batch` and
    `state` are donated: the (W,) slot pool lives on the device across
    polls and the host never rematerializes it.  Per poll the host
    pushes exactly one array, the flat f32 `xfer` (`_xfer_parts` gives
    its layout): `comp` (2, W) [slot; finish], `staged` (7, W), the
    scalars `n_stage` and `now`, the (2B,) [accepted; delay_ms]
    verdicts of the previous epoch's submit loop, and on sessions with
    a watchdog the (K,) resubmit charge.  It pulls one summary vector
    `[actions, req_idx, inflight_at, backoff, severity, next_defer]`
    (int fields ride exactly in f32 throughout).

    The resubmit charge is the per-class deficit charge for this
    epoch's watchdog resubmissions.  Sessions without a resilience
    layer leave it out of the buffer, and its absence is a static
    shape: that trace is the pre-resilience program.  Charged before
    dispatch so recovery traffic depresses its class's share this very
    epoch.

    `staged` not None selects the separate-argument form `(batch, state,
    prev, comp, staged, n_stage, now)`, a second pytree structure that
    packs its arguments on the device and carries no verdicts (`prev`
    applies as all accepted).  Neither `poll` nor the warm-up calls it;
    it exists only because bench/tests/test_scopes.py lowers the tick
    in that form.
    """
    w = batch.n
    b = min(max_grants, w)  # schedule_batch's grant width
    if staged is not None:
        xfer = jnp.concatenate([
            xfer.ravel(), staged.ravel(),
            jnp.stack([jnp.asarray(n_stage, jnp.float32), now]),
            jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.float32)])
    comp, staged, n_stage, now, verdicts, resub = _unpack_xfer(xfer, w, b)
    if prev is not None:
        state = _apply_body(policy, batch, state, prev,
                            verdicts[:b] != 0.0, verdicts[b:])
    if resub is not None:
        state = state._replace(sched=state.sched._replace(
            deficit=charge_resubmit(policy, state.sched.deficit, resub)))
    comp_slot = comp[0].astype(jnp.int32)
    finish = state.req.finish_ms.at[comp_slot].set(comp[1], mode="drop")
    state = state._replace(
        now_ms=now, req=state.req._replace(finish_ms=finish))
    state = _complete_and_timeout(policy, phys, batch, state)
    alive = (state.req.status == PENDING) | (state.req.status == INFLIGHT)
    batch, req, _ = _compact_and_admit(batch, state.req, alive, staged,
                                       n_stage)
    state = state._replace(req=req)
    d = schedule_batch(policy, batch, state,
                       max_grants=max_grants, backend=backend)
    # idle-sleep hint: earliest defer/Retry-After expiry already on the
    # books (this epoch's defers are added host-side from `backoff`)
    pend = req.status == PENDING
    next_defer = jnp.where(pend & (req.defer_until > now),
                           req.defer_until, jnp.inf).min()
    backoff = olc.defer_backoff(policy, d.severity, req.n_defers[d.req_idx])
    summary = jnp.concatenate([
        d.actions.astype(jnp.float32),
        d.req_idx.astype(jnp.float32),
        d.inflight_at.astype(jnp.float32),
        backoff,
        d.severity[None],
        next_defer[None],
    ])
    return batch, state, d, summary


def _freeze(tree) -> tuple:
    """Hashable value-key for a pytree of arrays (shape, dtype, bytes
    per leaf) — equality means numerically identical."""
    return tuple(
        (np.asarray(leaf).shape, str(np.asarray(leaf).dtype),
         np.asarray(leaf).tobytes())
        for leaf in jax.tree_util.tree_leaves(tree))


_TICK_CACHE: dict = {}


def _tick_for(policy: PolicyConfig, phys: ProviderPhysics,
              max_grants: int, backend: str):
    """Jitted fused tick with `policy` and `phys` baked in as trace
    constants.  A session's policy never changes mid-flight, and baking
    it buys the hot path twice: the per-poll dispatch flattens ~30
    argument leaves instead of ~60, and XLA folds the constant knobs
    through the program (the alloc-mode switch collapses to the one
    live branch, threshold ladders become immediates).  Cached by VALUE
    so every session with a numerically identical (policy, phys, B,
    backend) shares one compilation."""
    key = (_freeze(policy), _freeze(phys), max_grants, backend)
    fn = _TICK_CACHE.get(key)
    if fn is None:
        if len(_TICK_CACHE) > 64:
            _TICK_CACHE.clear()
        # RPL002 audit: donates positions 0-1 (the (W,) window pool and
        # device-state bundle). Callers reach this through `self._tick`,
        # declared in [tool.reprolint.donating-callables] so the
        # dataflow rule sees the donation through the bound method; both
        # call sites rebind the donated attributes in the same statement
        # (tests/test_serving_client.py::test_stale_post_donation_read_raises
        # is the runtime twin).
        fn = jax.jit(
            functools.partial(_fused_tick, policy, phys,
                              max_grants=max_grants, backend=backend),
            donate_argnums=(0, 1))
        _TICK_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------


class ClientSession:
    """Streaming three-layer client over an `AsyncProvider`.

    Lifecycle: `submit()` any number of requests over time (admission
    into the window is FIFO by submission order; keep arrivals
    nondecreasing when replaying a trace), `poll()` one decision epoch,
    `drain()` until everything submitted is terminal.  See the module
    docstring for the architecture.

    `phys` is the *client's* latency model — the unloaded-latency
    expectation the tail EMA normalizes observed completions against
    (client-observable signals only, per the paper; the benchmarks
    calibrate it against the real engine).

    `resilience` arms the watchdog (repro.client.resilience): per-
    request client-side deadlines, bounded-budget resubmission of stuck
    requests, and synthetic-abandon give-up — the machinery that keeps
    the session live against a provider that drops or wedges work.
    None (the default) is the trusting session: byte-identical device
    program, zero extra host work.  Duplicate-safe ingestion is NOT
    gated on this — at-least-once delivery is survived unconditionally.
    """

    def __init__(
        self,
        provider: AsyncProvider,
        policy: PolicyConfig,
        cfg: SessionConfig = SessionConfig(),
        *,
        clock: str = "wall",
        phys: ProviderPhysics | None = None,
        retry_policy: RetryPolicy | None = None,
        resilience: ResilienceConfig | None = None,
    ):
        if clock not in ("wall", "virtual"):
            raise ValueError(f"clock must be 'wall' or 'virtual', got {clock!r}")
        self.provider = provider
        self.policy = policy
        self.cfg = cfg
        self.clock = clock
        self.phys = phys if phys is not None else default_physics()
        self.retry_policy = retry_policy or honor_retry_after
        self.stats = SessionStats()
        self._spans: Optional[_PollSpans] = None

        w = cfg.window
        self._k = n_classes(policy)
        self._win_batch = empty_window_batch(w)
        self._dev_state = init_sim_state(w, self._k)._replace(
            req=empty_window_request_state(w))
        self._pending = None  # the BatchDecision to fold in next poll
        self._idle_cache: Optional[PollResult] = None
        # host mirrors (kept in lockstep with the device pool; float32
        # fields replay the device's own comparison chains bit-exactly)
        self._reqs: list[Request] = []
        self._arrival_ms: list[float] = []
        # columnar staging features, filled at submit() — queue pops
        # are a contiguous rid range, so staging is 7 list-slice
        # assigns into the transfer buffer's staged rows, not a per-row
        # loop
        self._cols: tuple[list, ...] = tuple([] for _ in range(7))
        self._queue: deque[int] = deque()
        self._slot_rid = np.full(w, -1, np.int64)
        self._slot_status = np.full(w, REJECTED, np.int32)
        self._slot_arrival = np.zeros(w, np.float32)
        self._slot_thresh = np.full(w, np.inf, np.float32)
        self._slot_finish = np.full(w, np.inf, np.float32)
        self._n_live = 0
        self._tickets: dict[int, int] = {}
        self._unfinished = 0
        self._t = 0
        self._t0: Optional[float] = None
        self._defer_hint = float("inf")
        self._timeout_mult = np.asarray(policy.timeout_mult, np.float32)
        self._watchdog = (Watchdog(resilience, self.phys)
                          if resilience is not None else None)
        # the reused per-poll transfer buffer (`_xfer_parts`) and its
        # parts as views.  A jitted call may read a NumPy argument in
        # place (zero-copy on the CPU) until it ends, so the buffer is
        # refilled only after the poll's blocking pull.
        self._bm = b = min(cfg.max_grants, w)  # schedule_batch's width
        comp, staged, self._x_nstage, self._x_now, verdicts, resub = \
            _xfer_parts(w, b)
        k_resub = self._k if self._watchdog is not None else 0
        self._xfer = np.zeros(verdicts.stop + k_resub, np.float32)
        self._comp = self._xfer[comp].reshape(2, w)
        self._comp[0] = w          # scatter sentinel: dropped by the set
        self._comp[1] = np.inf
        self._staged_px = self._xfer[staged].reshape(7, w)
        self._staged_px[_ST_TOKENS:_ST_P90 + 1] = 1.0
        self._staged_px[_ST_DEADLINE] = 1e9
        # [accepted; delay_ms] verdicts of the last submit loop
        self._accdelay = self._xfer[verdicts]
        self._accdelay[:b] = 1.0
        # (K,) per-class deficit charge for this epoch's resubmissions
        # (empty without a watchdog)
        self._resub_charge = self._xfer[resub]
        self._tick = _tick_for(policy, self.phys, cfg.max_grants,
                               cfg.backend)
        self._warmup()

    @property
    def _state(self) -> SimState:
        """Post-apply device state.  The fused tick leaves the previous
        epoch's transition pending (it is folded into the next poll);
        introspection flushes it first so callers always observe the
        state as if the epoch had been applied eagerly."""
        if self._pending is not None:
            ad, b = self._accdelay, self._bm
            self._dev_state = _apply_decisions(
                self.policy, self._win_batch, self._dev_state,
                self._pending, ad[:b] != 0.0, ad[b:].copy())
            self._pending = None
        return self._dev_state

    def _warmup(self) -> None:
        """Compile the session's device step against the (W, B, K)
        shapes before the clock starts: XLA compilation takes seconds,
        and a wall-clock session that compiles inside its first poll
        would burn that as session time — at time_scale >> 1 enough to
        blow every deadline before the first decision lands.  Both trace
        variants (with and without the leading apply) and the flush path
        are warmed; the throwaway buffers are re-initialized after.
        The transfer buffer is still in its initial state (no staged
        rows, all verdicts accepted, `now` 0), and the buffer's length
        already says whether the resubmit charge is in it."""
        w, k, bm = self.cfg.window, self._k, self._bm
        batch1, state1, d1, _ = self._tick(
            self._win_batch, self._dev_state, None, self._xfer)
        batch2, state2, d2, _ = self._tick(batch1, state1, d1, self._xfer)
        out = _apply_decisions(self.policy, batch2, state2, d2,
                               self._accdelay[:bm] != 0.0,
                               self._accdelay[bm:].copy())
        jax.block_until_ready(out.req.status)
        self._win_batch = empty_window_batch(w)
        self._dev_state = init_sim_state(w, k)._replace(
            req=empty_window_request_state(w))

    # --- clock --------------------------------------------------------
    def _wall_now_ms(self) -> float:
        if self._t0 is None:
            self._t0 = time.monotonic()
        return (time.monotonic() - self._t0) * 1e3 * self.cfg.time_scale

    def now_ms(self) -> float:
        if self.clock == "virtual":
            return float(np.float32(self._t) * np.float32(self.cfg.dt_ms))
        return self._wall_now_ms()

    # --- lifecycle ----------------------------------------------------
    def submit(self, req: Request) -> int:
        """Register a request; returns its session rid.  `arrival_s` is
        honored as given (0.0 = arrived at session start); wall-clock
        callers typically leave it 0 or stamp it with `now_ms()/1e3`."""
        rid = len(self._reqs)
        self._reqs.append(req)
        arrival = float(np.float32(req.arrival_s * 1000.0))
        self._arrival_ms.append(arrival)
        bkt = int(req.bucket)
        c = self._cols
        c[_ST_ARRIVAL].append(arrival)
        c[_ST_BUCKET].append(bkt)
        c[_ST_CLS].append(req.resolved_cls())
        c[_ST_TOKENS].append(float(req.max_new))
        c[_ST_P50].append(float(req.p50))
        c[_ST_P90].append(float(req.resolved_p90()))
        c[_ST_DEADLINE].append(_DEADLINE_PY[bkt])
        self._queue.append(rid)
        self._unfinished += 1
        self._idle_cache = None
        return rid

    @property
    def unfinished(self) -> int:
        return self._unfinished

    def enable_profiling(self) -> dict:
        """Turn on per-poll wall-time accounting and return the live
        accumulator dict.  Buckets (seconds, cumulative over profiled
        polls): `stage` — host-side work (completion ingest, retirement
        classification, arrival staging, mirror compaction), `dispatch`
        — the async fused-tick call (argument flatten + enqueue; the
        device executes concurrently with the mirror work), `pull` —
        the blocking device->host summary fetch, i.e. time actually
        waiting on the device, `grants` — the provider submit loop and
        verdict bookkeeping.  `polls` counts profiled epochs (the
        post-drain idle fast path is excluded — it does no device
        work).  Each phase of a profiled poll is also a profiler
        annotation `session.<phase>` (ingest, classify, staging,
        dispatch, mirrors, pull, grants; the first three and mirrors
        make up `stage`), seen by a running `jax.profiler` trace."""
        prof = {"stage": 0.0, "dispatch": 0.0, "pull": 0.0,
                "grants": 0.0, "polls": 0}
        self._spans = _PollSpans(prof)
        return prof

    def requests(self) -> list[Request]:
        return list(self._reqs)

    def _stage_admissions(self, now_ms: float, free: int) -> list[int]:
        """Pop arrived requests off the FIFO queue into the prefix of
        the persistent staging buffers (the window-admission rule the
        engine's `_compact_and_admit` applies to its arrival stream).
        Rows past the returned count are ignored by the device (masked
        by `n_stage`), so no reset is needed between polls."""
        rids = []
        while self._queue and len(rids) < free \
                and self._arrival_ms[self._queue[0]] <= now_ms:
            rids.append(self._queue.popleft())
        if not rids:
            return rids
        # rids popped FIFO off the monotone submit stream are a
        # contiguous range, so the staging features are column slices:
        # seven bulk assigns, no per-row work
        r0, n = rids[0], len(rids)
        px = self._staged_px
        for row, col in enumerate(self._cols):
            px[row, :n] = col[r0:r0 + n]
        return rids

    def _run_watchdog(self, now_ms: float, now32: np.float32, nl: int,
                      comp_by_rid: dict) -> None:
        """The resilience pass (repro.client.resilience): resubmit
        overdue in-flight requests within budget, give up — via a
        synthetic completion the retirement chain classifies
        timed_out -> ABANDONED — once the budget is gone and the slot's
        own timeout threshold has passed.  Mutates `comp_by_rid` (the
        pre-scatter completion view) and the ticket map only; device
        state is touched exclusively through the ordinary ingest path."""
        wd = self._watchdog
        for rid in wd.overdue(now_ms):
            if rid in comp_by_rid:
                continue  # landed this very epoch; retirement untracks it
            slot = int(np.searchsorted(self._slot_rid[:nl], rid))
            if slot >= nl or self._slot_rid[slot] != rid \
                    or self._slot_status[slot] != INFLIGHT:
                # defensive: no longer an in-flight slot (retirement
                # should have untracked it already)
                for t in wd.note_terminal(rid):
                    self._tickets.pop(t, None)
                continue
            r = self._reqs[rid]
            if wd.budget_left(rid):
                res = self.provider.submit(r, now_ms)
                if res.accepted:
                    # the attempts race: the old ticket stays mapped,
                    # first completion wins, the loser is discarded by
                    # dup-safe ingestion
                    self._tickets[res.ticket] = rid
                    wd.note_resubmit(rid, r, res.ticket, now_ms)
                    r.n_resubmits += 1
                    cls = min(max(r.resolved_cls(), 0), self._k - 1)
                    self._resub_charge[cls] += np.float32(r.p50)
                    self.stats.n_resubmitted += 1
                else:
                    # 429 on the recovery path: no budget consumed,
                    # re-check after the (sanitized) backoff
                    r.n_throttles += 1
                    delay = self.retry_policy(
                        sanitize_retry_after_ms(res.retry_after_ms),
                        r.n_throttles)
                    wd.note_bounced(rid, float(delay), now_ms)
                    self.stats.n_throttled += 1
                continue
            # budget exhausted: once the slot's e2e threshold has
            # passed (the same f32 comparison the classifier runs), a
            # synthetic completion stamped `now` is guaranteed to
            # classify timed_out -> ABANDONED on device and mirror
            # alike — give-up needs no second retirement mechanism
            if np.float32(now32 - self._slot_arrival[slot]) \
                    > self._slot_thresh[slot]:
                wd.give_up(rid)
                self.stats.n_gave_up += 1
                comp_by_rid[rid] = Completion(-1, float(now32), None)

    def poll(self, now_ms: Optional[float] = None) -> PollResult:
        """One decision epoch: one fused device step (apply previous
        verdicts, ingest completions, retire, compact + admit, dispatch)
        plus the host-side provider boundary (submit grants, collect 429
        verdicts).  O(W + B) regardless of session history length."""
        self._t += 1
        if now_ms is None:
            now_ms = self.now_ms() if self.clock == "wall" else float(
                np.float32(np.float32(self._t) * np.float32(self.cfg.dt_ms)))
        w, b = self.cfg.window, self._bm
        self.stats.n_polls += 1

        # post-drain fast path: an empty pool with nothing queued and
        # nothing in flight is a fixpoint (deficits reset on the first
        # idle epoch, the EMA holds, severity is constant), so the epoch
        # is replayed from the cached result with zero device work
        if (self._idle_cache is not None and not self._queue
                and not self._tickets and not self._unfinished):
            return self._idle_cache._replace(now_ms=now_ms)

        spans = self._spans
        if spans is not None:
            spans.to("ingest")
        now32 = np.float32(now_ms)
        nl = self._n_live

        # 1. provider completions -> comp scatter prefix + finish mirror.
        # Ingestion is duplicate-safe: the FIRST arrival for a rid wins,
        # and everything else — a redelivered ticket, a raced attempt
        # whose sibling already landed, a completion for a rid the
        # session already retired — is discarded HERE, before the
        # scatter, so the donated-buffer tick never sees a double-retire
        comps = self.provider.poll(now_ms)
        comp_by_rid: dict[int, Completion] = {}
        ncomp = 0
        for c in comps:
            rid = self._tickets.pop(c.ticket, None)
            if rid is None or rid in comp_by_rid:
                # dead ticket (dup redelivery / resolved race) or a
                # second arrival for the same rid within this epoch
                self.stats.n_dup_discarded += 1
                continue
            comp_by_rid[rid] = c
        if self._watchdog is not None:
            self._run_watchdog(now_ms, now32, nl, comp_by_rid)
        if comp_by_rid:
            rid_list = sorted(comp_by_rid)
            rids = np.asarray(rid_list, np.int64)
            slots = np.searchsorted(self._slot_rid[:nl], rids)
            if nl:
                live = ((slots < nl)
                        & (self._slot_rid[np.minimum(slots, nl - 1)] == rids))
            else:
                live = np.zeros(len(rids), bool)
            if not live.all():
                # late arrival: the rid no longer holds a window slot
                # (retired in an earlier epoch, e.g. after give-up)
                for i in np.nonzero(~live)[0]:
                    del comp_by_rid[rid_list[i]]
                    self.stats.n_late_discarded += 1
                rids, slots = rids[live], slots[live]
                rid_list = [r for r in rid_list if r in comp_by_rid]
            # asarray(..., f32) rounds each f64 element exactly like a
            # per-element np.float32() cast
            ncomp = len(rids)
            if ncomp:
                fins = np.asarray(
                    [comp_by_rid[r].finish_ms for r in rid_list], np.float32)
                self._comp[0, :ncomp] = slots
                self._comp[1, :ncomp] = fins
                self._slot_finish[slots] = fins

        if spans is not None:
            spans.to("classify")
        # 2. retirement classification on the f32 mirrors — the same
        # comparison chains `_complete_and_timeout` runs on the device
        # (sub/mul/compare round identically in f32; no FMA can form
        # across a comparison), so the verdicts match bit-for-bit
        st = self._slot_status[:nl]
        arr = self._slot_arrival[:nl]
        fin = self._slot_finish[:nl]
        th = self._slot_thresh[:nl]
        landed = (st == INFLIGHT) & (fin <= now32)
        timed_out = landed & ((fin - arr) > th)
        stale = (st == PENDING) & (arr <= now32) & ((now32 - arr) > th)
        dead = landed | stale
        completed: list[int] = []
        abandoned: list[int] = []
        for slot in np.nonzero(dead)[0]:
            rid = int(self._slot_rid[slot])
            r = self._reqs[rid]
            if landed[slot] and not timed_out[slot]:
                c = comp_by_rid.get(rid)
                r.status = "completed"
                r.finish_s = float(fin[slot]) / 1e3 \
                    if c is None else float(c.finish_ms) / 1e3
                if c is not None:
                    r.output = c.output
                completed.append(rid)
                self.stats.n_completed += 1
            else:
                # stale pending, or landed past the timeout multiple
                r.status = "abandoned"
                abandoned.append(rid)
                self.stats.n_abandoned += 1
            self._unfinished -= 1
            if self._watchdog is not None:
                # unmap every racing ticket this rid still holds: their
                # late completions are discarded at ingestion
                for t in self._watchdog.note_terminal(rid):
                    self._tickets.pop(t, None)
        alive = ((st == PENDING) | (st == INFLIGHT)) & ~dead
        n_alive = int(alive.sum())

        # 3. stage arrivals + 4. the fused device step
        if spans is not None:
            spans.to("staging")
        staged_rids = self._stage_admissions(now_ms, w - n_alive)
        n_stage = len(staged_rids)
        xfer = self._xfer
        xfer[self._x_nstage] = n_stage
        xfer[self._x_now] = now32
        if spans is not None:
            spans.to("dispatch")
        self._win_batch, self._dev_state, d, summary = self._tick(
            self._win_batch, self._dev_state, self._pending, xfer)
        if spans is not None:
            spans.to("mirrors")
        # the dispatch is async: the mirror bookkeeping below depends
        # only on host state, so it runs while the device executes —
        # the blocking summary pull comes after

        # 5. mirror compaction (lockstep with the device scatter)
        nt = n_alive + n_stage
        self._slot_rid[:n_alive] = self._slot_rid[:nl][alive]
        self._slot_status[:n_alive] = st[alive]
        self._slot_arrival[:n_alive] = arr[alive]
        self._slot_thresh[:n_alive] = th[alive]
        self._slot_finish[:n_alive] = fin[alive]
        if n_stage:
            sl = slice(n_alive, nt)
            self._slot_rid[sl] = staged_rids
            self._slot_status[sl] = PENDING
            px = self._staged_px
            self._slot_arrival[sl] = px[_ST_ARRIVAL, :n_stage]
            self._slot_thresh[sl] = (
                self._timeout_mult[px[_ST_BUCKET, :n_stage].astype(np.int64)]
                * px[_ST_DEADLINE, :n_stage])
            self._slot_finish[sl] = np.inf
            for rid in staged_rids:
                self._reqs[rid].status = "pending"
        self._slot_rid[nt:self._n_live] = -1
        self._slot_status[nt:self._n_live] = REJECTED
        self._n_live = nt

        # 6. submit grants (decision order); collect 429 verdicts
        if spans is not None:
            spans.to("pull")
        summary = np.asarray(summary)  # the one device->host pull
        if spans is not None:
            spans.to("grants")
        # the tick has ended, so its transfer buffer may be reset now
        if ncomp:
            self._comp[0, :ncomp] = w
            self._comp[1, :ncomp] = np.inf
        if self._watchdog is not None and self._resub_charge.any():
            self._resub_charge[:] = 0.0
        actions = summary[0:b].astype(np.int32)
        idxs = summary[b:2 * b].astype(np.int32)
        infl_at = summary[2 * b:3 * b].astype(np.int32)
        backoff = summary[3 * b:4 * b]
        severity = np.float32(summary[4 * b])
        dev_next_defer = float(summary[4 * b + 1])
        ad = self._accdelay
        ad[:b] = 1.0
        ad[b:] = 0.0
        req_rids = np.full(b, -1, np.int64)
        admitted, deferred, rejected, throttled = [], [], [], []
        for g in range(b):
            a = actions[g]
            if a == IDLE:
                continue
            slot = idxs[g]
            rid = int(self._slot_rid[slot])
            req_rids[g] = rid
            r = self._reqs[rid]
            if a == olc.ADMIT:
                res = self.provider.submit(
                    r, now_ms, inflight_hint=int(infl_at[g]))
                if res.accepted:
                    self._tickets[res.ticket] = rid
                    r.status = "inflight"
                    r.submit_s = now_ms / 1e3
                    self._slot_status[slot] = INFLIGHT
                    admitted.append(rid)
                    self.stats.n_admitted += 1
                    if self._watchdog is not None:
                        self._watchdog.note_admit(rid, r, res.ticket, now_ms)
                else:
                    ad[g] = 0.0
                    r.n_throttles += 1
                    # f32-array store rounds the f64 delay identically
                    # to an explicit np.float32 cast.  The hint is
                    # sanitized first: a hostile (negative/NaN)
                    # Retry-After must not mint a defer expiry in the
                    # past or poison the idle-sleep hint
                    ad[b + g] = self.retry_policy(
                        sanitize_retry_after_ms(res.retry_after_ms),
                        r.n_throttles)
                    throttled.append(rid)
                    self.stats.n_throttled += 1
            elif a == olc.DEFER:
                r.n_defers += 1
                deferred.append(rid)
                self.stats.n_deferred += 1
            else:  # REJECT
                r.status = "rejected"
                self._slot_status[slot] = REJECTED
                rejected.append(rid)
                self.stats.n_rejected += 1
                self._unfinished -= 1

        # 7. the device transition folds into the next poll's step (its
        # verdicts ride in the transfer buffer)
        self._pending = d
        self.stats.peak_inflight = max(
            self.stats.peak_inflight, self.provider.inflight())
        hint = dev_next_defer
        if deferred:
            hint = min(hint, float(
                (now32 + backoff[actions == olc.DEFER]).min()))
        if throttled:
            bounced = ad[:b] == 0.0
            hint = min(hint, float((now32 + ad[b:][bounced]).min()))
        self._defer_hint = hint

        if spans is not None:
            spans.end()
        progressed = bool(
            completed or abandoned or rejected or admitted or deferred
            or throttled or staged_rids)
        result = PollResult(
            now_ms=now_ms, actions=actions, req_rids=req_rids,
            severity=severity, completed=completed, abandoned=abandoned,
            rejected=rejected, admitted=admitted, deferred=deferred,
            throttled=throttled, n_live=self._n_live, progressed=progressed)
        if (not progressed and not self._unfinished and not self._queue
                and not self._tickets and nt == 0 and ncomp == 0):
            self._idle_cache = result
        return result

    # --- drain --------------------------------------------------------
    def _idle_sleep(self, now_ms: float) -> None:
        """Sleep until the next actionable instant instead of spinning:
        the next queued arrival, the earliest defer/Retry-After expiry,
        or the provider's next-event hint — capped so an unhintable
        transport still gets re-polled."""
        cands = []
        if self._queue:
            cands.append(self._arrival_ms[self._queue[0]])
        if np.isfinite(self._defer_hint):
            cands.append(self._defer_hint)
        if self._watchdog is not None:
            nd = self._watchdog.next_deadline_ms()
            if np.isfinite(nd):
                cands.append(nd)
        pe = self.provider.next_event_ms(now_ms)
        if pe is not None:
            cands.append(pe)
        # a candidate already due (e.g. a queued arrival stuck behind a
        # full window) is not a wakeup signal — keeping it would clamp
        # the sleep to zero and busy-spin until the blocker clears
        cands = [c for c in cands if c > now_ms]
        target = min(cands) if cands else now_ms + self.cfg.max_idle_sleep_ms
        target = min(target, now_ms + self.cfg.max_idle_sleep_ms)
        sleep_s = (target - now_ms) / 1e3 / self.cfg.time_scale
        if sleep_s > 0:
            self.stats.n_idle_sleeps += 1
            time.sleep(sleep_s)

    def _live_slot_report(self, limit: int = 16) -> str:
        """Human-readable snapshot of the occupied window slots for
        liveness diagnostics: (rid, status, age_ms) triples."""
        names = {PENDING: "pending", INFLIGHT: "inflight"}
        nl = self._n_live
        now = np.float32(self.now_ms())
        rows = []
        for slot in range(nl):
            st = int(self._slot_status[slot])
            if st not in names:
                continue
            rows.append(
                f"(rid={int(self._slot_rid[slot])} {names[st]} "
                f"age={float(now - self._slot_arrival[slot]):.0f}ms)")
        extra = f" ... +{len(rows) - limit} more" if len(rows) > limit else ""
        return " ".join(rows[:limit]) + extra

    def drain(self, max_polls: Optional[int] = None,
              max_idle_ms: Optional[float] = None) -> list[Request]:
        """Poll until every submitted request is terminal.  Wall-clock
        sessions sleep through idle epochs; virtual sessions advance one
        tick per poll.  Ends with one settling epoch that compacts the
        last retirements out of the pool and primes the idle fast path
        (subsequent polls on the drained session are host-only no-ops).
        Returns the session's requests.

        `max_idle_ms` is the liveness guard: if no poll makes progress
        for that much session time — the signature of a completion that
        will never arrive (e.g. silently dropped by the provider) — the
        drain raises a diagnostic RuntimeError naming the live slots,
        the provider's inflight count, and the last-progress timestamp,
        instead of sleeping forever.  None (the default) preserves the
        wait-forever contract for trusted transports."""
        n = 0
        last_progress: Optional[float] = None
        while self._unfinished:
            r = self.poll()
            n += 1
            if last_progress is None or r.progressed:
                last_progress = r.now_ms
            if self._unfinished and max_polls is not None and n >= max_polls:
                raise RuntimeError(
                    f"drain: {self._unfinished} request(s) still live "
                    f"after {n} polls")
            if (max_idle_ms is not None and self._unfinished
                    and r.now_ms - last_progress > max_idle_ms):
                raise RuntimeError(
                    f"drain: no progress for "
                    f"{r.now_ms - last_progress:.0f} ms (cap "
                    f"{max_idle_ms:.0f} ms): {self._unfinished} "
                    f"unfinished, {self.provider.inflight()} "
                    f"provider-inflight, last progress at "
                    f"t={last_progress:.0f} ms (now t={r.now_ms:.0f} ms); "
                    f"live slots: {self._live_slot_report()}")
            if self.clock == "wall" and not r.progressed:
                self._idle_sleep(r.now_ms)
        if not self._queue and not self._tickets \
                and self._idle_cache is None:
            self.poll()  # settle: retire bookkeeping, prime the fast path
        return list(self._reqs)
