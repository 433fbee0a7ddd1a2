"""Candidate lanes played on the device per plan over the window: the
playback kernel's lane counter (``batchsim_jax.compile_stats()['lanes']``)
over the window, divided by the plans. 0 means the backend choice kept
every lane on the host."""


def read(ctx):
    plans = ctx.counters.get("plans")
    if not plans or "lanes" not in ctx.counters:
        return None
    return ctx.counters["lanes"] / plans
