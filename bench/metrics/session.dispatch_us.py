"""`session.dispatch_us`: microseconds per poll in the session's `dispatch` bucket
(`ClientSession.enable_profiling`): the jit call of the fused tick: argument flatten and enqueue.
Averaged over every profiled poll of the traced run's window."""


def read(ctx):
    prof = ctx.get("profile")
    if not prof or not prof["polls"]:
        return None
    return prof["dispatch"] / prof["polls"] * 1e6
