"""Named device scopes for the stages of the decision tick.

The engine's scan body (`sim.engine.sim_tick`) and the live session's
device step (`client.session._fused_tick`) run the same stage
functions.  Each stage traces its ops under a `jax.named_scope`, so the
compiled program's `op_name` metadata (and, on a TPU, the profiler's
device ops) name the stage an op belongs to.  A scope is metadata only:
it adds no op, and the compiled program differs from an unscoped one in
`op_name` alone.  Scopes nest; an op belongs to its innermost stage.
"""
from __future__ import annotations

import functools

import jax

RETIRE = "tick.retire"      # completions, timeouts, stale abandonment
ADMIT = "tick.admit"        # window compaction + admission of arrivals
ROUTE = "tick.route"        # fleet endpoint choice (P > 1 only)
ORDER = "tick.order"        # eligibility and ranked candidates
OVERLOAD = "tick.overload"  # the severity score
GRANT = "tick.grant"        # the per-grant DRR + admission-ladder loop
APPLY = "tick.apply"        # the decisions' state transition

STAGES = (RETIRE, ADMIT, ROUTE, ORDER, OVERLOAD, GRANT, APPLY)


def scoped(name: str):
    """Decorator: trace the function under `jax.named_scope(name)`.  A
    fresh scope object per call, so traces in two threads never share
    one scope's saved state."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco
