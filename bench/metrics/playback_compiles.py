"""Playback kernels traced (compiled, or loaded from the compilation cache)
inside the window: the rise of ``batchsim_jax.compile_stats()['trace_count']``
over it. Set-up warms the shapes it knows; what it missed lands here."""


def read(ctx):
    return ctx.counters.get("compiles")
