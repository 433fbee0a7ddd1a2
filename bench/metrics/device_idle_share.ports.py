"""Share of the traced window in which no operation ran on the chip, in a
port-limited planning cell: 1 - union of device-op intervals / window."""
from bench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    return tracing.idle_share(ctx.trace)
