"""Public jit'd wrapper: interpret=True on CPU, compiled on TPU.

Pads the queue axis to a lane-aligned block multiple (mask=False
padding) so callers can hand in any N — e.g. the 10^5-deep queues of
the batch-dispatch benchmark — while the kernel always sees TPU-tileable
block shapes.  Padding is shape-static, so jit specializes once per
(N, blk).
"""
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.sched_score.sched_score import (
    sched_score_argmax as _argmax_kernel,
    sched_score_topb as _topb_kernel,
)

_LANE = 128  # TPU lane width: block shapes must stay a multiple of this


def _pad_queue(wait, cost, urgency, mask, blk: int, route=None):
    """Pad the queue axis to a block multiple with inert lanes
    (mask=False, unit cost, zero route).  Padding is shape-static, so
    jit specializes once per (n, blk)."""
    n = wait.shape[0]
    # shrink the block for short queues without losing lane alignment
    blk = min(blk, max(_LANE, -(-n // _LANE) * _LANE))
    pad = (-n) % blk
    if pad:
        zf = jnp.zeros((pad,), wait.dtype)
        wait = jnp.concatenate([wait, zf])
        cost = jnp.concatenate([cost, jnp.ones((pad,), cost.dtype)])
        urgency = jnp.concatenate([urgency, zf])
        mask = jnp.concatenate([mask, jnp.zeros((pad,), bool)])
        if route is not None:
            route = jnp.concatenate([route, jnp.zeros((pad,), route.dtype)])
    return wait, cost, urgency, mask, route, blk


def sched_score_argmax(wait, cost, urgency, mask, weights, route=None, *,
                       blk: int = 2048):
    """wait/cost/urgency: (n,) f32; mask: (n,) bool; weights: (4,)
    [w_wait, w_size, w_urg, ref_tokens]. Returns (best_idx i32, best_score).
    Any n is accepted — the queue is padded internally to a lane-aligned
    block multiple with mask=False lanes.  `route` (n,) f32 enables the
    fleet route term with a (5,) weights vector [..., w_route]."""
    wait, cost, urgency, mask, route, blk = _pad_queue(
        wait, cost, urgency, mask, blk, route)
    return _argmax_kernel(wait, cost, urgency, mask, weights, route, blk=blk,
                          interpret=interpret_mode())


def sched_score_topb(wait, cost, urgency, mask, weights, b: int, route=None,
                     *, blk: int = 2048):
    """Fused score + partial top-B over a queue of any length n >= b.

    Returns (idx (b,) i32, score (b,) f32) in release order, matching
    `lax.top_k` over the masked scores including first-occurrence
    tie-breaking.  Padding lanes are mask=False: their NEG scores rank
    after every real lane's (real masked lanes share the NEG value but
    precede the padding in index order), so with b <= n a padded index
    can never reach the output.  `route` (n,) f32 enables the fleet
    route term with a (5,) weights vector [..., w_route].
    """
    n = wait.shape[0]
    b = min(int(b), n)
    wait, cost, urgency, mask, route, blk = _pad_queue(
        wait, cost, urgency, mask, blk, route)
    return _topb_kernel(wait, cost, urgency, mask, weights, route, b=b,
                        blk=blk, interpret=interpret_mode())

