"""Playback kernels traced (compiled, or loaded from the compilation cache)
inside the window of a port-limited planning cell: the rise of
``batchsim_jax.compile_stats()['trace_count']`` over it. Set-up warms the
blocked shapes the mix's warm-up grid meets; a shape it missed lands here,
as a late compile inside the window."""


def read(ctx):
    return ctx.counters.get("compiles")
