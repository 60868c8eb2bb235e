"""`tick.pull_us`: microseconds per poll in the session's `pull` bucket
(`ClientSession.enable_profiling`): the blocking device-to-host summary pull, i.e. time the host waits on the device tick.
Averaged over every profiled poll of the traced run's window."""


def read(ctx):
    prof = ctx.get("profile")
    if not prof or not prof["polls"]:
        return None
    return prof["pull"] / prof["polls"] * 1e6
