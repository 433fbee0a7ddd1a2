"""Device milliseconds of the playback kernel per plan in the traced span of
a port-limited planning cell: the summed device time of the jitted ``play``
program (module ``jit_play``) divided by the plans completed while the
profiler ran."""
from bench import tracing


def read(ctx):
    plans = ctx.counters.get("traced_plans")
    if ctx.trace is None or not plans:
        return None
    try:
        seconds, _ = tracing.program_seconds(ctx.trace, "play")
    except tracing.MissingEvent:
        return None
    return seconds / plans * 1e3
