"""Share of the traced window in which no operation ran, averaged over the
chips of a collective-step cell: 1 - mean union of op intervals / window."""
from bench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    return tracing.idle_share(ctx.trace)
