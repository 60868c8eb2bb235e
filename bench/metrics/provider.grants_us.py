"""`provider.grants_us`: microseconds per poll in the session's `grants` bucket
(`ClientSession.enable_profiling`): the provider submit loop and verdict bookkeeping after the pull.
Averaged over every profiled poll of the traced run's window."""


def read(ctx):
    prof = ctx.get("profile")
    if not prof or not prof["polls"]:
        return None
    return prof["grants"] / prof["polls"] * 1e6
