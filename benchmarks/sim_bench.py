"""Batch fabric engine benchmark: scalar sparse loop vs vectorized playback.

Four measurement tiers plus the plan-serving path:

  - ``scoring`` tier (n = 96): the planner's event-scoring workload — a
    30+-candidate set (every deduped periodic / rs-early / ag-late /
    exact-dp schedule for all three collectives at one n) completion-timed
    once by the scalar per-chunk `FabricSim` loop and once by a single
    `batchsim.batch_run` call.  Gates (exit 1): batched >= ``--min-speedup``
    x faster, every lane on the vectorized fast path, every lane statically
    certified (`repro.analysis.certifier` — the row reports the certified
    fraction), certified playback no slower than the guard-based
    ``certify=False`` path, and completions equal to the scalar loop within
    1e-9 relative.
  - ``scale`` tier (n in {768, 1536}): batched-only — the scalar engine is
    not run at all at this scale (it would take minutes per grid point);
    the row records wall time and a completion checksum so regressions in
    the engine itself are caught by `benchmarks.check_regression`.
  - ``jax`` tier (n = 1536, 256 lanes): the JAX ``jit``/``vmap`` backend
    (`core.batchsim_jax`) vs the NumPy batch engine on a wide hop-capped
    certified lane set.  Gates: jax >= ``--min-jax-speedup`` x faster than
    NumPy (warm, after the one-off XLA compile the row also records), every
    completion within 1e-6 relative of the NumPy engine, and playback
    bit-stable across runs.
  - ``jax-scale`` tier (n in {8192, 32768}): JAX-only — grids the NumPy
    batch engine never runs (its per-hop dispatch alone would take minutes
    per batch); rows record wall time, bit-stability, and a completion
    checksum.
  - plan-cache serving: repeated `PlanRequest` traffic through one
    `Planner`, recording hit/miss counts and cold vs cached plan latency.

Run via ``make sim-bench``; results land in BENCH_sim_scale.json.  The CI
bench job runs ``--smoke`` (scoring + jax tiers) against the committed
baseline; the nightly workflow runs the full grid including the n >= 768
and n >= 8192 tiers.  docs/batch_engine.md turns these rows into the
backend performance model.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.compile_cache import enable_compile_cache

MB = 1024.0 ** 2
DELTA = 1e-3


def _candidate_lanes(n: int, m: float, max_lanes: int | None = None):
    """Deduped all-kind candidate schedules at one n (shared S => one batch)."""
    from repro.core import PAPER_DEFAULT
    from repro.core import schedules as S
    from repro.core.batchsim import BatchLane

    seen, lanes = set(), []
    for kind in ("a2a", "rs", "ag"):
        for _, sched in S.candidate_schedules(kind, n, m, PAPER_DEFAULT):
            key = (sched.kind, sched.x)
            if key in seen:
                continue
            seen.add(key)
            lanes.append(BatchLane(schedule=sched, m_bytes=m))
    return lanes[:max_lanes] if max_lanes else lanes


def _jax_lanes(n: int, m: float, lanes_target: int = 256,
               hop_cap: int = 300):
    """Wide certified lane set for the jax tiers (deterministic).

    Serving-shaped workload: the deduped candidate set at one n, capped at
    ``hop_cap`` total hops per schedule (the near-static tail of the
    candidate set costs both engines minutes without changing the
    comparison), tiled with a 1% payload ramp out to ``lanes_target`` lanes.
    All lanes are uniform, so under the paper regime all are certified —
    exactly the population the JAX backend exists for.
    `benchmarks.verify_gate` reconstructs these lanes from the committed row
    (lanes / hop_cap) to re-audit their schedules.
    """
    from repro.core.batchsim import BatchLane, compile_tape

    base = [lane for lane in _candidate_lanes(n, m)
            if sum(compile_tape(lane.schedule).hops) <= hop_cap]
    if not base:
        raise ValueError(f"hop_cap={hop_cap} filtered out every candidate "
                         f"schedule at n={n}")
    lanes, rep = [], 0
    while len(lanes) < lanes_target:
        for lane in base:
            lanes.append(BatchLane(schedule=lane.schedule,
                                   m_bytes=m * (1.0 + 0.01 * rep)))
        rep += 1
    return lanes[:lanes_target]


def bench_jax(n: int = 1536, m: float = 4 * MB, chunks: int = 4,
              lanes_target: int = 256, hop_cap: int = 300) -> dict:
    """JAX vs NumPy batch engine on one wide certified batch."""
    from repro.core import PAPER_DEFAULT
    from repro.core.batchsim import batch_run
    from repro.core.batchsim_jax import compile_stats

    cm = PAPER_DEFAULT.replace(delta=DELTA)
    lanes = _jax_lanes(n, m, lanes_target=lanes_target, hop_cap=hop_cap)

    def run(backend):
        t0 = time.perf_counter()
        res = batch_run(lanes, cm, chunks_per_msg=chunks, backend=backend)
        return res, time.perf_counter() - t0

    # warm the shared memoized layers (tapes, certificates) on a sliver so
    # neither timed engine is charged the other's cold-cache work; the XLA
    # compile itself is deliberately NOT warmed — jax_cold_wall_s records it
    batch_run(lanes[:2], cm, chunks_per_msg=chunks)
    traces0 = compile_stats()["trace_count"]
    res_np, numpy_wall = run("numpy")
    res_cold, jax_cold_wall = run("jax")      # includes per-bucket XLA compile
    res_jax, jax_wall = run("jax")            # steady state
    res_jax2, _ = run("jax")                  # run-to-run determinism probe
    import numpy as np
    worst_rel = float(np.max(np.abs(res_jax.completion - res_np.completion)
                             / np.maximum(np.abs(res_np.completion), 1e-30)))
    bit_stable = (np.array_equal(res_cold.node_done, res_jax.node_done)
                  and np.array_equal(res_jax.node_done, res_jax2.node_done)
                  and np.array_equal(res_jax.step_done, res_jax2.step_done))
    return {
        "tier": "jax", "n": n, "r": 2, "m_bytes": m, "chunks": chunks,
        "delta": DELTA, "lanes": len(lanes), "hop_cap": hop_cap,
        "backend": res_jax.backend,
        "numpy_wall_s": round(numpy_wall, 4),
        "jax_cold_wall_s": round(jax_cold_wall, 4),
        "jax_wall_s": round(jax_wall, 4),
        "jax_compiles": compile_stats()["trace_count"] - traces0,
        "jax_speedup": round(numpy_wall / max(jax_wall, 1e-9), 2),
        "fast_lanes": int(res_jax.fast_path.sum()),
        "certified_lanes": int(res_jax.certified.sum()),
        "worst_rel_diff": float(f"{worst_rel:.3e}"),
        "bit_stable": bool(bit_stable),
        "completion_checksum": float(res_jax.completion.sum()),
    }


def bench_jax_scale(n: int, m: float = 4 * MB, chunks: int = 2,
                    lanes_target: int = 64, hop_cap: int = 400) -> dict:
    """JAX-only: grids the NumPy batch engine never runs."""
    from repro.core import PAPER_DEFAULT
    from repro.core.batchsim import batch_run, clear_tape_caches

    cm = PAPER_DEFAULT.replace(delta=DELTA)
    lanes = _jax_lanes(n, m, lanes_target=lanes_target, hop_cap=hop_cap)
    clear_tape_caches()  # first contact at this scale: include tape compile
    t0 = time.perf_counter()
    res = batch_run(lanes, cm, chunks_per_msg=chunks, backend="jax")
    jax_cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res2 = batch_run(lanes, cm, chunks_per_msg=chunks, backend="jax")
    jax_wall = time.perf_counter() - t0
    import numpy as np
    bit_stable = (np.array_equal(res.node_done, res2.node_done)
                  and np.array_equal(res.step_done, res2.step_done))
    return {
        "tier": "jax-scale", "n": n, "r": 2, "m_bytes": m, "chunks": chunks,
        "delta": DELTA, "lanes": len(lanes), "hop_cap": hop_cap,
        "backend": res.backend,
        "numpy_wall_s": None,      # deliberately never run at this scale
        "jax_cold_wall_s": round(jax_cold_wall, 4),
        "jax_wall_s": round(jax_wall, 4),
        "jax_compiles": None,      # cold/warm split already covers compiles
        "jax_speedup": None,
        "fast_lanes": int(res.fast_path.sum()),
        "certified_lanes": int(res.certified.sum()),
        "worst_rel_diff": None,
        "bit_stable": bool(bit_stable),
        "completion_checksum": float(res.completion.sum()),
    }


def bench_scoring(n: int = 96, m: float = 4 * MB, chunks: int = 8) -> dict:
    from repro.core import PAPER_DEFAULT, FabricSim
    from repro.core.batchsim import batch_run

    cm = PAPER_DEFAULT.replace(delta=DELTA)
    lanes = _candidate_lanes(n, m)

    def run_scalar():
        return [FabricSim(chunks_per_msg=chunks, mode="sparse")
                .run(lane.schedule, m, cm).completion for lane in lanes]

    # steady-state timing: one untimed pass per engine warms every memoized
    # layer (step structure, link-offset gcds, compiled tapes, fast-path
    # certificates) so neither timed side is charged the other's cold-cache
    # work
    run_scalar()
    batch_run(lanes, cm, chunks_per_msg=chunks)
    batch_run(lanes, cm, chunks_per_msg=chunks, certify=False)
    t0 = time.perf_counter()
    scalar = run_scalar()
    scalar_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = batch_run(lanes, cm, chunks_per_msg=chunks)
    batched_wall = time.perf_counter() - t0
    # guard-based path: same batch with certificates disabled, so the
    # canonical-order guards run their per-step bookkeeping (the pre-certifier
    # behaviour); certified playback must not be slower than this
    t0 = time.perf_counter()
    batch_run(lanes, cm, chunks_per_msg=chunks, certify=False)
    guard_wall = time.perf_counter() - t0
    worst_rel = max(
        abs(float(b) - s) / max(abs(s), 1e-30)
        for b, s in zip(res.completion, scalar, strict=True))
    return {
        "tier": "scoring", "n": n, "r": 2, "m_bytes": m, "chunks": chunks,
        "delta": DELTA, "lanes": len(lanes),
        "scalar_wall_s": round(scalar_wall, 4),
        "batched_wall_s": round(batched_wall, 4),
        "guard_wall_s": round(guard_wall, 4),
        "batched_speedup": round(scalar_wall / max(batched_wall, 1e-9), 2),
        "fast_lanes": int(res.fast_path.sum()),
        "certified_lanes": int(res.certified.sum()),
        "worst_rel_diff": float(f"{worst_rel:.3e}"),
        "completion_checksum": float(res.completion.sum()),
    }


def bench_scale(n: int, m: float = 4 * MB, chunks: int = 4,
                max_lanes: int = 30) -> dict:
    """Batched-only: grids the scalar loop cannot touch in CI time."""
    from repro.core import PAPER_DEFAULT
    from repro.core.batchsim import batch_run, clear_tape_caches

    cm = PAPER_DEFAULT.replace(delta=DELTA)
    lanes = _candidate_lanes(n, m, max_lanes=max_lanes)
    clear_tape_caches()  # first contact at this scale: include tape compile
    t0 = time.perf_counter()
    res = batch_run(lanes, cm, chunks_per_msg=chunks)
    batched_wall = time.perf_counter() - t0
    return {
        "tier": "scale", "n": n, "r": 2, "m_bytes": m, "chunks": chunks,
        "delta": DELTA, "lanes": len(lanes),
        "scalar_wall_s": None,     # deliberately never run at this scale
        "batched_wall_s": round(batched_wall, 4),
        "guard_wall_s": None,      # guard-path A/B is a scoring-tier gate
        "batched_speedup": None,
        "fast_lanes": int(res.fast_path.sum()),
        "certified_lanes": int(res.certified.sum()),
        "worst_rel_diff": None,
        "completion_checksum": float(res.completion.sum()),
    }


def bench_plan_cache(n: int = 96, repeats: int = 20) -> dict:
    """Serving path: repeated PlanRequest traffic through one Planner."""
    from repro.core import PAPER_DEFAULT
    from repro.planner import Planner, PlanRequest

    cm = PAPER_DEFAULT.replace(delta=DELTA)
    reqs = [PlanRequest(kind=kind, n=n, m_bytes=(i + 1) * MB, cost_model=cm,
                        fabric="ocs-sim")
            for kind in ("a2a", "rs") for i in range(2)]
    planner = Planner(cache_size=64, sim_chunks=8)
    t0 = time.perf_counter()
    for req in reqs:
        planner.plan(req)
    cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(repeats):
        planner.plan_batch(reqs)
    warm_wall = time.perf_counter() - t0
    info = planner.cache_info()
    warm_per_plan_us = warm_wall * 1e6 / (repeats * len(reqs))
    cold_per_plan_us = cold_wall * 1e6 / len(reqs)
    return {
        "n": n, "distinct_requests": len(reqs),
        "total_plans": len(reqs) * (repeats + 1),
        "hits": info.hits, "misses": info.misses,
        "hit_rate": round(info.hits / max(1, info.hits + info.misses), 4),
        "cold_plan_us": round(cold_per_plan_us, 1),
        "cached_plan_us": round(warm_per_plan_us, 1),
        "cache_amortization": round(cold_per_plan_us
                                    / max(warm_per_plan_us, 1e-3), 1),
    }


def check_gates(rows: list[dict], cache: dict, min_speedup: float,
                min_jax_speedup: float = 3.0) -> list[str]:
    errors = []
    for row in rows:
        key = f"tier={row['tier']} n={row['n']}"
        if row["fast_lanes"] != row["lanes"]:
            errors.append(f"{key}: only {row['fast_lanes']}/{row['lanes']} "
                          f"lanes on the vectorized fast path (uniform lanes "
                          f"must never fall back)")
        if row["certified_lanes"] != row["lanes"]:
            errors.append(f"{key}: only {row['certified_lanes']}/"
                          f"{row['lanes']} lanes statically certified "
                          f"(uniform candidate lanes under alpha_s > 0 must "
                          f"all hold fast-path certificates)")
        if row["tier"] in ("jax", "jax-scale"):
            if row["backend"] != "jax":
                errors.append(f"{key}: resolved backend {row['backend']!r} "
                              f"!= 'jax' (certified lanes must have run on "
                              f"the XLA kernel)")
            if not row["bit_stable"]:
                errors.append(f"{key}: JAX playback not bit-stable "
                              f"run-to-run")
            if row["tier"] == "jax":
                if row["jax_speedup"] < min_jax_speedup:
                    errors.append(f"{key}: jax_speedup {row['jax_speedup']} "
                                  f"< {min_jax_speedup} (warm XLA playback "
                                  f"vs the NumPy batch engine)")
                if row["worst_rel_diff"] > 1e-6:
                    errors.append(f"{key}: jax vs numpy completion drift "
                                  f"{row['worst_rel_diff']} > 1e-6")
            continue
        if row["tier"] != "scoring":
            continue
        if row["batched_speedup"] < min_speedup:
            errors.append(f"{key}: batched_speedup {row['batched_speedup']} "
                          f"< {min_speedup}")
        if row["worst_rel_diff"] > 1e-9:
            errors.append(f"{key}: batched vs scalar completion drift "
                          f"{row['worst_rel_diff']} > 1e-9")
        if row["batched_wall_s"] > 1.25 * row["guard_wall_s"]:
            errors.append(f"{key}: certified playback {row['batched_wall_s']}"
                          f"s slower than the guard-based path "
                          f"{row['guard_wall_s']}s x 1.25 (the certificate "
                          f"must never cost more than the guards it waives)")
    if cache["misses"] != cache["distinct_requests"]:
        errors.append(f"plan cache: {cache['misses']} misses != "
                      f"{cache['distinct_requests']} distinct requests")
    expected_hits = cache["total_plans"] - cache["distinct_requests"]
    if cache["hits"] != expected_hits:
        errors.append(f"plan cache: {cache['hits']} hits != expected "
                      f"{expected_hits}")
    return errors


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("--smoke", action="store_true",
                    help="scoring + jax tiers + plan cache only (CI; the "
                         "committed baseline still covers every row produced)")
    ap.add_argument("--scale-ns", default="768,1536",
                    help="comma-separated n values for the batched-only tier")
    ap.add_argument("--jax-ns", default="8192,32768",
                    help="comma-separated n values for the jax-only tier")
    ap.add_argument("--min-speedup", type=float, default=10.0,
                    help="min batched/scalar wall ratio on the scoring tier")
    ap.add_argument("--min-jax-speedup", type=float, default=3.0,
                    help="min warm jax/numpy wall ratio on the jax tier")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.core.batchsim_jax import jax_available

    rows = [bench_scoring()]
    if jax_available():
        rows.append(bench_jax())
    else:
        print("# skip jax tiers: jax is not importable", file=sys.stderr)
    if not args.smoke:
        for n in (int(v) for v in args.scale_ns.split(",")):
            rows.append(bench_scale(n))
        if jax_available():
            for spec in (v for v in args.jax_ns.split(",") if v):
                n = int(spec)
                # deeper hop budget at the top of the grid: the candidate
                # tail grows with n, and only XLA is paying for it
                rows.append(bench_jax_scale(
                    n, lanes_target=64 if n <= 8192 else 32,
                    hop_cap=400 if n <= 8192 else 600))
    cache = bench_plan_cache()

    print("tier,n,lanes,scalar_wall_s,batched_wall_s,guard_wall_s,speedup,"
          "fast_lanes,certified_lanes,worst_rel_diff")
    for row in rows:
        if row["tier"] in ("jax", "jax-scale"):
            print(f"{row['tier']},{row['n']},{row['lanes']},"
                  f"numpy={row['numpy_wall_s']},jax={row['jax_wall_s']},"
                  f"cold={row['jax_cold_wall_s']},{row['jax_speedup']},"
                  f"{row['fast_lanes']},{row['certified_lanes']},"
                  f"{row['worst_rel_diff']}")
            continue
        print(f"{row['tier']},{row['n']},{row['lanes']},"
              f"{row['scalar_wall_s']},{row['batched_wall_s']},"
              f"{row['guard_wall_s']},{row['batched_speedup']},"
              f"{row['fast_lanes']},{row['certified_lanes']},"
              f"{row['worst_rel_diff']}")
    print(f"# plan cache: {cache['hits']} hits / {cache['misses']} misses "
          f"(rate {cache['hit_rate']}), cold {cache['cold_plan_us']} us -> "
          f"cached {cache['cached_plan_us']} us "
          f"({cache['cache_amortization']}x)")

    errors = check_gates(rows, cache, args.min_speedup,
                         min_jax_speedup=args.min_jax_speedup)
    if errors:
        # gate first: never overwrite the committed baseline with bad data
        for e in errors:
            print(f"# FAIL: {e}", file=sys.stderr)
        sys.exit(1)
    if args.json:
        out = {
            "meta": {
                "what": "scalar sparse FabricSim vs vectorized batch engine "
                        "(core.batchsim) vs the JAX jit/vmap backend "
                        "(core.batchsim_jax) wall time, plus the LRU "
                        "plan-cache serving path (BENCH_sim_scale baseline)",
                "delta": DELTA,
            },
            "rows": rows,
            "plan_cache": cache,
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
