"""Readings that set the limits of ``correct``: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For every seed, in one process, runs the cell as ``run.py`` does (short
window, no trace), takes the numbers its check compared, and computes the
same numbers for the control on the same answers: the plain reference put
in the program's place in the precision below the configuration's.

- plans (float64): the reference's completions in float32;
- collectives (bfloat16 dispatch, float32 gradients): XLA's all_to_all of
  the buffer rounded to float8 (e4m3), and XLA's psum in bfloat16.

Prints one JSON line per seed: ``{"seed", "program": {...}, "control": {...}}``.
A limit lies above every program reading and below every control reading.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def plan_control(ctx) -> dict:
    import numpy as np

    from bench.drivers.plan_closed_loop import fabric_of
    from bench.reference import plans

    numbers, _ = plans.compare(ctx.checked, ctx.config["n"],
                               fabric_of(ctx.config),
                               ctx.config["planner"]["sim_chunks"],
                               rank_tol=ctx.mix["limits"]["score_rel_gap"],
                               control_dtype=np.float32)
    return numbers


def collective_control(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    progs, (chain, g, o_in, _) = ctx.checked

    @jax.jit
    def gap(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))

    ref_a2a = progs["a2a_reference"]
    a2a = max(float(gap(ref_a2a(a.astype(jnp.float8_e4m3fn)).astype(
        jnp.bfloat16), ref_a2a(a))[0]) for a in chain[:-1])
    low = progs["allreduce_reference"](g.astype(jnp.bfloat16),
                                       o_in.astype(jnp.bfloat16))
    diff, scale = (float(v) for v in gap(
        low, progs["allreduce_reference"](g, o_in)))
    return {"a2a_max_abs_diff": a2a, "allreduce_rel_err": diff / scale}


CONTROLS = {"plan_closed_loop": plan_control,
            "collective_step": collective_control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import harness, traffic

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    device = harness.device_check(int(cell["chips"]))
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.RunContext(
            cell=cell, config=harness.load_config(cell["config"]),
            mix=traffic.load_mix(cell["traffic"]), seed=seed,
            seconds=args.seconds, traced=False, started=time.perf_counter(),
            device=device, config_code=harness.config_module(cell["config"]),
            peaks=harness.peaks_for(device["kind"]))
        out = harness.execute(bench, ctx)
        control = CONTROLS[ctx.mix["driver"]](ctx)
        ctx.checked = None
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": {k: c["value"] for k, c in out["checks"].items()},
            "control": control, "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
