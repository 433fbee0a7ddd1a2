"""Share of the hops the playback kernel played over the window that the
Section 3.7 block floor added: the rise of
``batchsim_jax.compile_stats()['blocked_hops']`` over that of ``['hops']``.
Hops the full-port fabric would also take are the rest; 0 means no played
candidate crossed a block boundary over a circuit. A program without these
counters reads nothing."""


def read(ctx):
    hops = ctx.counters.get("hops")
    if not hops or "blocked_hops" not in ctx.counters:
        return None
    return ctx.counters["blocked_hops"] / hops
