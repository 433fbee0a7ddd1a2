"""Candidate lanes played on the device per plan over the window of a
port-limited planning cell: the playback kernel's lane counter
(``batchsim_jax.compile_stats()['lanes']``) over the window, divided by the
plans. Above 0 shows the backend choice sends blocked-ring candidate sets
to the chip; 0 means it kept every lane on the host."""


def read(ctx):
    plans = ctx.counters.get("plans")
    if not plans or "lanes" not in ctx.counters:
        return None
    return ctx.counters["lanes"] / plans
