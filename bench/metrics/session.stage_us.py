"""`session.stage_us`: microseconds per poll in the session's `stage` bucket
(`ClientSession.enable_profiling`): host-side work of a poll: completion ingest, retirement classification on the f32 mirrors, arrival staging, mirror compaction.
Averaged over every profiled poll of the traced run's window."""


def read(ctx):
    prof = ctx.get("profile")
    if not prof or not prof["polls"]:
        return None
    return prof["stage"] / prof["polls"] * 1e6
