"""Drive the collective-step cell on 4 virtual CPU devices, at a tiny size.

    python bench/tests/_collective_worker.py

Runs the cell's driver as ``run.py`` would (no chip check) once sound, once
per fault planted underneath its timed programs, and once for the control,
and prints one JSON object: ``{"<case>": {"correct": ..., "checks": ...}}``.
"""
import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = " ".join(
    [t for t in os.environ.get("XLA_FLAGS", "").split()
     if not t.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.collectives as collectives  # noqa: E402
from bench import control, harness, traffic  # noqa: E402

CELL = "moe-ep4.layer-step"
BRUCK_A2A = collectives.bruck_all_to_all


def tiny_context(seed: int):
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, CELL)
    cfg = harness.load_config(cell["config"])
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_experts=8)
    cfg["deployment"]["tokens_per_chip"] = 32
    ctx = harness.RunContext(
        cell=cell, config=cfg,
        mix=traffic.load_mix(cell["traffic"]), seed=seed, seconds=0.5,
        traced=False, started=time.perf_counter(),
        config_code=harness.config_module(cell["config"]),
        peaks=harness.peaks_for("TPU v5 lite"))
    return bench, ctx


def run(seed: int, **patches):
    saved = {k: getattr(collectives, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(collectives, k, v)
        bench, ctx = tiny_context(seed)
        out = harness.execute(bench, ctx)
    finally:
        for k, v in saved.items():
            setattr(collectives, k, v)
    return out, ctx


def unchanged(x, axis):
    """A step that returns its state unchanged: no exchange at all."""
    return x


def half_batch(x, axis):
    """Half of the rows left out of the exchange."""
    out = BRUCK_A2A(x, axis)
    half = x.shape[1] // 2
    return out.at[:, half:].set(x[:, half:])


def altered(x, axis):
    """One element of the answer altered where it is produced."""
    out = BRUCK_A2A(x, axis)
    return out.at[0, 0, 0].add(jnp.asarray(1, out.dtype))


def no_exchange_allreduce(x, axis, *schedules):
    """The all-reduce with the exchange left out."""
    return x * jax.lax.axis_size(axis)


def main():
    results = {}
    out, ctx = run(2**31 + 7)
    results["sound"] = {"correct": out["correct"], "checks": out["checks"],
                        "metrics": out["metrics"],
                        "impl": ctx.counters["allreduce_impl"],
                        "control": control.collective_control(ctx)}
    for name, patch in (("unchanged", {"bruck_all_to_all": unchanged}),
                        ("half_batch", {"bruck_all_to_all": half_batch}),
                        ("altered", {"bruck_all_to_all": altered}),
                        ("no_exchange",
                         {"bruck_all_reduce": no_exchange_allreduce})):
        out, _ = run(11, **patch)
        results[name] = {"correct": out["correct"], "checks": out["checks"]}
    print(json.dumps(results))


if __name__ == "__main__":
    main()
