"""The benchmark's all-reduce program against the chip-to-chip peak.

Least bytes a chip has to send in an all-reduce of its buffer,
2 (N - 1) / N x the buffer, over the published interconnect rate per chip
(``ici_bits_per_s`` / 8 in bench/peaks.json), divided by the device time of
one call of ``bench_moe_allreduce`` in the trace. Counted from shapes alone.
"""
from bench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("allreduce_bytes_per_chip"):
        return None
    try:
        seconds, calls = tracing.program_seconds(ctx.trace,
                                                 "bench_moe_allreduce")
    except tracing.MissingEvent:
        return None
    n = ctx.counters["chips"]
    least = 2 * (n - 1) / n * ctx.counters["allreduce_bytes_per_chip"]
    return least / (ctx.peaks["ici_bits_per_s"] / 8) / (seconds / calls)
