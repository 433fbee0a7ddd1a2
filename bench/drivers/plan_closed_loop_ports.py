"""Closed-loop planning on a port-limited OCS: `plan_closed_loop`'s client,
with every request carrying the configuration's ``ports``.

One client sends a request, waits for its plan, sends the next. Set-up
builds the `Planner` the configuration states and plans the mix's warm-up
grid, so every playback shape the window meets is compiled (or loaded from
the compilation cache) before it. The window plans the seed's requests
(`bench.traffic.plan_requests`) until ``--seconds`` have passed and the plan
under way has finished. After it, a sample of the window's plans drawn from
the seed, and the slowest one, are checked against the plain reference of
the blocked ring (`bench.reference.blocked`).

End-to-end: ``plans_per_s`` and ``plan_ms_p90``, as `plan_closed_loop`
takes them. Counters for the per-layer metrics: plans, and over the window
the playback kernel's lanes, compiles, and hops in all and added by the
block floor (`batchsim_jax.compile_stats()`; a program without a counter
leaves it out).
"""
from __future__ import annotations

import sys
import time

from bench import harness, traffic, tracing
from bench.drivers.plan_closed_loop import fabric_of, percentile
from bench.reference import blocked as blocked_reference

COUNTERS = {"lanes": "lanes", "compiles": "trace_count",
            "hops": "hops", "blocked_hops": "blocked_hops"}


def build(config: dict):
    """The planner and a request maker, as the configuration states them."""
    from repro.core.cost_model import CostModel
    from repro.planner import FabricKind, Planner, PlanRequest

    fab = fabric_of(config)
    cm = CostModel(alpha_s=fab["alpha_s"], alpha_h=fab["alpha_h"],
                   bandwidth=fab["bandwidth"], delta=fab["delta"])
    planner = Planner(**config["planner"])
    fabric = FabricKind(config["fabric"])

    def request(kind: str, m_bytes: float):
        return PlanRequest(kind=kind, n=config["n"], r=config["r"],
                           m_bytes=m_bytes, fabric=fabric, cost_model=cm,
                           ports=config["ports"])

    return planner, request


def run(ctx: harness.RunContext) -> harness.DriverResult:
    import jax

    from repro.core import batchsim_jax

    config, mix = ctx.config, ctx.mix
    planner, request = build(config)
    for kind, m in mix["warmup"]:
        planner.plan(request(kind, float(m)))
    with tracing.span("generate"):
        requests = traffic.plan_requests(mix, ctx.seed)
    profiler = tracing.Profiler(ctx.traced)

    setup_s = time.perf_counter() - ctx.started
    before = batchsim_jax.compile_stats()
    latencies, answers = [], []
    failed = 0
    errors: list[str] = []
    trace_until = min(ctx.seconds, float(mix["trace_seconds"]))
    traced_plans = 0
    profiler.start()
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    for kind, m in requests:
        start = time.perf_counter()
        if start >= deadline:
            break
        if profiler.running and start - t0 >= trace_until:
            profiler.stop()
            traced_plans = len(latencies)
        try:
            with tracing.span("plan"):
                res = planner.plan(request(kind, m))
        except Exception as exc:  # a failed plan is counted, not fatal
            failed += 1
            errors.append(f"{kind} m={m!r}: {type(exc).__name__}: {exc}")
            res = None
        latencies.append(time.perf_counter() - start)
        answers.append((kind, m, res))
    window = time.perf_counter() - t0
    if profiler.running:
        profiler.stop()
        traced_plans = len(latencies)
    after = batchsim_jax.compile_stats()
    mem = harness.memory_peak_bytes(jax.devices())

    done = [i for i, a in enumerate(answers) if a[2] is not None]
    picked = {done[j] for j in traffic.check_sample(
        len(done), int(mix["check_sample"]), ctx.seed)}
    if done:
        picked.add(max(done, key=lambda i: latencies[i]))
    sample = [answers[i] for i in sorted(picked)]
    with tracing.span("reference"):
        numbers, broken = blocked_reference.compare(
            sample, config["n"], fabric_of(config),
            config["planner"]["sim_chunks"], config["ports"],
            rank_tol=mix["limits"]["score_rel_gap"])
    numbers["failed_plans"] = float(failed)
    for line in (errors + broken)[:20]:
        print(f"bench: {line}", file=sys.stderr)
    limits = mix["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}

    ctx.counters.update(plans=len(latencies), traced_plans=traced_plans)
    ctx.counters.update({name: after[key] - before[key]
                         for name, key in COUNTERS.items() if key in after})
    ctx.checked = sample
    ctx.trace = profiler.reduce()
    return harness.DriverResult(
        attempted=len(latencies), failed=failed,
        metrics={"setup_s": setup_s,
                 "plans_per_s": len(done) / window,
                 "plan_ms_p90": percentile(latencies, 90) * 1e3},
        checks=checks, memory_peak_bytes=mem)
