"""Pallas ordering kernels — the scheduler's production score backend.

One fused kernel over an `(nf, N)` feature matrix (rows: wait, cost,
urgency[, route]; the eligibility mask is always the LAST row) and an
`(nf,)` weight vector:

* `sched_score_topb` — the top-B scores/indices for batched dispatch.
* `sched_score_argmax` — its b=1 column: the (index, score) of the
  best eligible candidate.

The optional fourth feature row is the fleet route cost (DESIGN.md
§10); `has_route` is trace-static, so the four-row program compiled
for single-provider runs is untouched when routing is off.

Contract (RPL005, enforced by reprolint + tests/test_kernels.py):
every kernel has a jnp oracle in `ref.py` that must match
**bit-exactly**, not approximately — score floats and tie-breaking
index order both. The oracles are jitted so both sides share XLA's
instruction selection (see ref.py's docstring for why eager oracles
drift by one ulp). Import surface: `ops` picks the mode (compiled
on TPU, interpret mode on CPU, an error elsewhere), `ref` holds the
oracles.
"""
from repro.kernels.sched_score import ops, ref  # noqa: F401
