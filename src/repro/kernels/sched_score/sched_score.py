"""Ordering-layer scoring kernel (the paper's §3.1.2 hot spot at
production queue depths).

`sched_score_topb` fuses the feasible-set score

    score = w1 * (wait / cost) - w2 * (cost / ref) + w3 * urgency

with a partial top-B: one tiled pass computes each block's scores in
VMEM, extracts the block's local top-B by B successive masked argmaxes,
and merges them into a running best-B set (a strict replace-worst
merge).  The merge is associative with the blocks processed in index
order, and the strict (`>` only) eviction rule makes ties resolve to
the earliest index — bit-identical to `lax.top_k`'s first-occurrence
semantics, which the windowed scheduler's bit-exact contract relies on.
The final block selection-sorts the set into (idx, score) rows, best
first.  Compared with `lax.top_k` over the full (K, N) score matrix this
streams each element once and keeps only O(B) state.
`sched_score_argmax` is its b=1 column.

TPU layout: every vector the kernel touches is 2-D — feature rows are
(1, blk) slices, reductions keep their dims, and the running set and
both outputs are lane-dense (1, 128) rows (the (b,) results are sliced
outside the kernel).  Mosaic cannot store a scalar to VMEM, so no value
is ever written element by element; the weights are scalars and live in
SMEM.

Fleet route term (DESIGN.md §10): the kernel optionally takes a fifth
feature row `route` (per-request predicted queue delay at its best
endpoint, seconds) and a fifth weight `w_route`, subtracting
`w_route * route` from the score.  Presence is static (`has_route`),
so single-provider callers compile the exact four-row program; the
feature axis is the sublane (second-to-last) dimension, so growing it
4 -> 5 leaves the lane-aligned minor axis untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
_BPAD = 128  # lane width of the running set and the output rows;
             # entries >= b are inert (+inf/-inf guards)
_BIG = 2**31 - 1


def _score_rows(arr_ref, w_ref, has_route: bool):
    """Score evaluation over (1, blk) feature rows [wait, cost, urg(,
    route), mask] against SMEM weights [w1, w2, w3, ref_tok(, w_route)].
    The route term is subtracted — a congested best endpoint ranks the
    request later.  `has_route` is trace-static, so the four-row program
    is unchanged byte for byte when off."""
    m = 4 if has_route else 3
    wait = arr_ref[0:1, :]
    cost = arr_ref[1:2, :]
    urg = arr_ref[2:3, :]
    mask = arr_ref[m:m + 1, :]
    w1, w2, w3, ref_tok = w_ref[0, 0], w_ref[0, 1], w_ref[0, 2], w_ref[0, 3]

    c = jnp.maximum(cost, 1.0)
    score = w1 * (wait / c) - w2 * (c / ref_tok) + w3 * urg
    if has_route:
        score = score - w_ref[0, 4] * arr_ref[3:4, :]
    return score, mask


def _topb_kernel(w_ref, arr_ref, out_idx_ref, out_score_ref,
                 best_s_ref, best_i_ref, *, blk: int, nb: int, b: int,
                 has_route: bool):
    bi = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _BPAD), 1)
    in_set = lane < b

    @pl.when(bi == 0)
    def _init():
        # -inf sentinels rank below every candidate (masked lanes carry
        # the finite NEG), so real entries always displace them first
        best_s_ref[...] = jnp.full((1, _BPAD), -jnp.inf, jnp.float32)
        best_i_ref[...] = jnp.full((1, _BPAD), -1, jnp.int32)

    score, mask = _score_rows(arr_ref, w_ref, has_route)
    score = jnp.where(mask > 0, score, NEG)
    gidx = bi * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    best_s = best_s_ref[...]
    best_i = best_i_ref[...]

    # local top-B by successive masked argmax (first occurrence: the
    # smallest index holding the max), merged into the running set one
    # candidate at a time.  Candidates arrive in (score desc, idx asc)
    # order and blocks run in index order, so a candidate that merely
    # *ties* the running worst is always the later index — the strict
    # `>` eviction below is exactly top_k's first-occurrence tie-breaking.
    for _ in range(b):
        s = jnp.max(score, keepdims=True)                       # (1, 1)
        j = jnp.min(jnp.where(score == s, gidx, _BIG), keepdims=True)
        score = jnp.where(gidx == j, -jnp.inf, score)

        cur = jnp.where(in_set, best_s, jnp.inf)
        worst = jnp.min(cur, keepdims=True)
        # evict the worst entry; among equal-score entries the one with
        # the LARGEST index (it ranks last under first-occurrence order).
        # Resolve to a single lane: -1 sentinels are not unique, so an
        # index match alone could hit several lanes at once.
        evict_i = jnp.max(jnp.where(cur == worst, best_i, -2), keepdims=True)
        cand = in_set & (cur == worst) & (best_i == evict_i)
        hit = (lane == jnp.max(jnp.where(cand, lane, -1), keepdims=True)) \
            & (s > worst)
        best_s = jnp.where(hit, s, best_s)
        best_i = jnp.where(hit, j, best_i)
    best_s_ref[...] = best_s
    best_i_ref[...] = best_i

    @pl.when(bi == nb - 1)
    def _finish():
        # selection-sort the set into release order: score desc, ties by
        # ascending index (first occurrence) — lax.top_k's output order;
        # rank r lands in lane r of the lane-dense output rows
        rem_s = best_s
        out_i = jnp.zeros((1, _BPAD), jnp.int32)
        out_s = jnp.full((1, _BPAD), NEG, jnp.float32)
        for r in range(b):
            cur = jnp.where(in_set, rem_s, -jnp.inf)
            m = jnp.max(cur, keepdims=True)
            sel = jnp.min(jnp.where(cur == m, best_i, _BIG), keepdims=True)
            out_i = jnp.where(lane == r, sel, out_i)
            out_s = jnp.where(lane == r, m, out_s)
            rem_s = jnp.where((cur == m) & (best_i == sel), -jnp.inf, rem_s)
        out_idx_ref[...] = out_i
        out_score_ref[...] = out_s


def _stack_features(wait, cost, urgency, mask, route):
    """(rows, n) feature stack: [wait, cost, urg(, route), mask].  The
    mask row stays last so `has_route` only inserts, never reorders."""
    rows = [wait, cost, urgency]
    if route is not None:
        rows.append(route)
    rows.append(mask.astype(jnp.float32))
    return jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("b", "blk", "interpret"))
def sched_score_topb(wait, cost, urgency, mask, weights, route=None, *,
                     b: int, blk: int = 2048, interpret: bool = False):
    """Fused score + partial top-B.  wait/cost/urgency: (n,) f32; mask:
    (n,) bool; weights: (4,) [w_wait, w_size, w_urg, ref_tokens].
    Returns (idx (b,) i32, score (b,) f32) in release order (best
    first), matching `lax.top_k` over the masked score vector including
    first-occurrence tie-breaking.  n must be a multiple of blk (callers
    pad with mask=False); requires b <= min(blk, _BPAD) and b <= n so
    sentinels can never reach the output.  `route` (n,) f32 enables the
    fleet route term with a (5,) weights vector [..., w_route]."""
    n = wait.shape[0]
    blk = min(blk, n)
    assert n % blk == 0, "pad the queue to a block multiple"
    assert 0 < b <= min(blk, _BPAD) and b <= n, (b, blk, n)
    nb = n // blk
    has_route = route is not None
    nf = 5 if has_route else 4
    arr = _stack_features(wait, cost, urgency, mask, route)  # (nf, n)
    w = weights.astype(jnp.float32)[None, :]                 # (1, nf)

    kernel = functools.partial(_topb_kernel, blk=blk, nb=nb, b=b,
                               has_route=has_route)
    idx, score = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            # the whole (1, nf) weight vector as SMEM scalars
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nf, blk), lambda g: (0, g)),
        ],
        out_specs=[
            pl.BlockSpec((1, _BPAD), lambda g: (0, 0)),
            pl.BlockSpec((1, _BPAD), lambda g: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, _BPAD), jnp.int32),
            jax.ShapeDtypeStruct((1, _BPAD), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, _BPAD), jnp.float32),
            pltpu.VMEM((1, _BPAD), jnp.int32),
        ],
        interpret=interpret,
    )(w, arr)
    return idx[0, :b], score[0, :b]


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def sched_score_argmax(wait, cost, urgency, mask, weights, route=None, *,
                       blk: int = 2048, interpret: bool = False):
    """Fused score + masked argmax: the b=1 column of
    `sched_score_topb`.  Returns (best_idx i32, best_score f32) with
    first-occurrence tie-breaking; n must be a multiple of blk."""
    idx, score = sched_score_topb(wait, cost, urgency, mask, weights, route,
                                  b=1, blk=blk, interpret=interpret)
    return idx[0], score[0]
