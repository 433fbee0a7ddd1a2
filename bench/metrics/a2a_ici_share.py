"""The benchmark's all-to-all program against the chip-to-chip peak.

Least bytes a chip has to send in an all-to-all of its buffer,
(N - 1) / N x the buffer, over the published interconnect rate per chip
(``ici_bits_per_s`` / 8 in bench/peaks.json), divided by the device time of
one call of ``bench_moe_a2a`` in the trace. Counted from shapes alone, so it
reads the same work whatever algorithm does the exchange.
"""
from bench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("a2a_bytes_per_chip"):
        return None
    try:
        seconds, calls = tracing.program_seconds(ctx.trace, "bench_moe_a2a")
    except tracing.MissingEvent:
        return None
    n = ctx.counters["chips"]
    least = (n - 1) / n * ctx.counters["a2a_bytes_per_chip"]
    return least / (ctx.peaks["ici_bits_per_s"] / 8) / (seconds / calls)
