"""Pure-jnp oracles for the fused scheduler scoring kernels.

Every oracle mirrors its kernel's optional fleet route term: pass
`route` (per-request predicted queue delay, seconds) with a (5,)
weights vector [w_wait, w_size, w_urg, ref_tokens, w_route] and the
score subtracts `w_route * route`; omit it and the four-weight program
is unchanged.

The oracles are jitted: the kernels they certify are jitted wrappers,
and exact-equality parity requires both sides to see the same XLA:CPU
instruction selection.  The five-term score ends in `score - w * route`,
which XLA contracts to a single-rounded FMA under jit but not in eager
per-op dispatch (`lax.optimization_barrier` is stripped by the
optimizer, so pinning cannot force the eager shape) — an eager oracle
would sit one ulp off the kernel on ~a quarter of random inputs.
"""
import functools

import jax
import jax.numpy as jnp

NEG = -1e30


def _scores(wait, cost, urgency, mask, weights, route=None):
    w1, w2, w3, ref_tok = weights[0], weights[1], weights[2], weights[3]
    c = jnp.maximum(cost, 1.0)
    score = w1 * (wait / c) - w2 * (c / ref_tok) + w3 * urgency
    if route is not None:
        score = score - weights[4] * route
    return jnp.where(mask, score, NEG)


@jax.jit
def sched_score_argmax_ref(wait, cost, urgency, mask, weights, route=None):
    score = _scores(wait, cost, urgency, mask, weights, route)
    i = jnp.argmax(score)
    return i.astype(jnp.int32), score[i]


@functools.partial(jax.jit, static_argnames=("b",))
def sched_score_topb_ref(wait, cost, urgency, mask, weights, b: int,
                         route=None):
    """Full-width ranking oracle: `lax.top_k` over the masked scores
    (first-occurrence tie-breaking).  Returns (idx (b,), score (b,))."""
    score = _scores(wait, cost, urgency, mask, weights, route)
    vals, idx = jax.lax.top_k(score, b)
    return idx.astype(jnp.int32), vals

