"""Back-to-back steps of a layer's collectives on a mesh of every chip.

One step is the communication of one MoE layer's training step, as the
configuration's ``deployment`` states it: ``a2a_per_step`` all-to-alls of
the dispatch buffer through the program's `bruck_all_to_all` (dispatch and
combine, forward and backward), then one all-reduce of the layer's
replicated gradients through the training path of ``launch/train.py``:
`gradient_sync_plan` picks the implementation, `bruck_all_reduce` runs
where it picks ``bruck`` and ``psum`` otherwise, and the sum is divided by
the chips. Each collective is one jitted program with a name of the
benchmark's own (``bench_moe_a2a``, ``bench_moe_allreduce``).

Steps are chained by data: each all-to-all takes the one before it, and the
all-reduce adds its previous result to the chips' own gradients, so every
step moves new data and the chips' inputs differ. At most two steps are in
flight. The window ends when the last step's results are ready.

After the window, the inputs and outputs of one step drawn from the seed
are checked against XLA's own ``all_to_all`` and ``psum`` on the same
inputs: the all-to-alls exactly, the all-reduce by its relative error.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, traffic, tracing

AXIS = "ep"


def sizes(config: dict, config_code, chips: int) -> dict:
    """Per-chip shapes of one step, from the configuration."""
    dep = config["deployment"]
    rows = dep["tokens_per_chip"] * config["num_experts_per_tok"] // chips
    return {"chips": chips, "a2a_shape": (chips, rows, config["hidden_size"]),
            "grad_elems": int(config_code.replicated_grad_elems(config))}


def programs(mesh, grad_elems: int):
    """The two timed programs and XLA's own references, jitted."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.collectives import (bruck_all_reduce, bruck_all_to_all,
                                   gradient_sync_plan)

    n = mesh.devices.size
    plan = gradient_sync_plan(n, 4.0 * grad_elems)

    def bench_moe_a2a(x):
        return bruck_all_to_all(x, AXIS)

    def bench_moe_allreduce(g, o):
        s = g + o
        if plan.impl == "bruck":
            s = bruck_all_reduce(s, AXIS, plan.rs_schedule, plan.ag_schedule)
        else:
            s = jax.lax.psum(s, AXIS)
        return s / n

    def bench_moe_a2a_reference(x):
        return jax.lax.all_to_all(x, AXIS, 0, 0)

    def bench_moe_allreduce_reference(g, o):
        return jax.lax.psum(g + o, AXIS) / n

    def smap(fn, n_in):
        specs = (P(AXIS),) * n_in
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                     out_specs=P(AXIS), check_vma=False))

    return {"a2a": smap(bench_moe_a2a, 1),
            "allreduce": smap(bench_moe_allreduce, 2),
            "a2a_reference": smap(bench_moe_a2a_reference, 1),
            "allreduce_reference": smap(bench_moe_allreduce_reference, 2),
            "impl": plan.impl}


def make_inputs(mesh, sz: dict, seed: int):
    """Dispatch buffer, the chips' own gradients and a zero start, made on
    the chips in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n = sz["chips"]
    shard = NamedSharding(mesh, P(AXIS))
    a2a_global = (n * sz["a2a_shape"][0],) + tuple(sz["a2a_shape"][1:])
    grad_global = (n * sz["grad_elems"],)
    key = int(traffic.rng(seed, "collective_data").integers(2**31))

    def make(k):
        kx, kg = jax.random.split(jax.random.key(k))
        return (jax.random.normal(kx, a2a_global, jnp.bfloat16),
                jax.random.normal(kg, grad_global, jnp.float32),
                jnp.zeros(grad_global, jnp.float32))

    return jax.jit(make, out_shardings=(shard, shard, shard))(key)


def compare(progs, kept):
    """(worst |a2a - XLA| over the step's all-to-alls, all-reduce's
    relative error against XLA's psum) for one kept step."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))

    chain, g, o_in, o_out = kept
    a2a = max(float(gap(b, progs["a2a_reference"](a))[0])
              for a, b in zip(chain, chain[1:]))
    diff, scale = (float(v) for v in gap(
        o_out, progs["allreduce_reference"](g, o_in)))
    return a2a, diff / scale


def run(ctx: harness.RunContext) -> harness.DriverResult:
    import jax
    from jax.sharding import Mesh

    config, mix = ctx.config, ctx.mix
    chips = int(config["deployment"]["chips"])
    devices = jax.devices()[:chips]
    mesh = Mesh(np.asarray(devices), (AXIS,))
    sz = sizes(config, ctx.config_code, chips)
    progs = programs(mesh, sz["grad_elems"])
    a2a, allreduce = progs["a2a"], progs["allreduce"]
    per_step = int(mix["a2a_per_step"])
    x, g, o = make_inputs(mesh, sz, ctx.seed)
    jax.block_until_ready((a2a(x), allreduce(g, o)))   # compile or load
    check_at = traffic.check_step(mix, ctx.seed)
    profiler = tracing.Profiler(ctx.traced)

    setup_s = time.perf_counter() - ctx.started
    steps = traced_steps = 0
    kept = last = pending = None
    trace_until = min(ctx.seconds, float(mix["trace_seconds"]))
    profiler.start()
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        if profiler.running and now - t0 >= trace_until:
            jax.block_until_ready(pending)
            profiler.stop()
            traced_steps = steps
        with tracing.span("step_dispatch"):
            chain = [x]
            for _ in range(per_step):
                chain.append(a2a(chain[-1]))
            o_next = allreduce(g, o)
        last = (tuple(chain), g, o, o_next)
        if steps == check_at:
            kept = last
        if pending is not None:
            jax.block_until_ready(pending)
        pending = (chain[-1], o_next)
        x, o = chain[-1], o_next
        steps += 1
    jax.block_until_ready(pending)
    window = time.perf_counter() - t0
    if profiler.running:
        profiler.stop()
        traced_steps = steps
    mem = harness.memory_peak_bytes(devices)
    kept = kept or last
    last = pending = chain = None

    with tracing.span("reference"):
        a2a_gap, ar_err = compare(progs, kept)
    limits = mix["limits"]
    numbers = {"a2a_max_abs_diff": a2a_gap, "allreduce_rel_err": ar_err}
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    rows, d = sz["a2a_shape"][1:]
    ctx.counters.update(
        steps=steps, traced_steps=traced_steps, chips=chips,
        a2a_per_step=per_step, allreduce_impl=progs["impl"],
        a2a_bytes_per_chip=chips * rows * d * 2,
        allreduce_bytes_per_chip=sz["grad_elems"] * 4)
    ctx.checked = (progs, kept)
    ctx.trace = profiler.reduce()
    return harness.DriverResult(
        attempted=steps, failed=0,
        metrics={"setup_s": setup_s, "step_ms": window / steps * 1e3},
        checks=checks, memory_peak_bytes=mem)
