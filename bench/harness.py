"""Shared machinery of a benchmark run: cells, device, cache, metrics, result.

A run is ``run.py`` -> `main` -> the cell's driver (``bench/drivers``), which
sets up, measures for ``--seconds`` and checks its answers, then the cell's
per-layer metric readers (``bench/metrics``) with ``--trace 1``. This module
knows no cell, configuration, traffic mix or metric by name: it finds each
under the name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(SystemExit):
    """The machine lacks the chips the cell asks for; no result is printed."""


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no cell {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    path = BENCH_DIR / "configs" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(name: str):
    """The configuration's companion code (``configs/<name>.py``), if any."""
    path = BENCH_DIR / "configs" / f"{name}.py"
    return load_module(path, f"bench_config_{name}") if path.is_file() else None


def metrics_for(bench: dict, cell: dict, traced: bool) -> list[dict]:
    """The metric entries a run of ``cell`` reports, in file order."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries
            if cell["name"] in m.get("workloads", [cell["name"]])]


def device_check(chips: int) -> dict:
    """The devices JAX reports; refuse anything but exactly ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"bench: jax.devices()[0].platform is {d0.platform!r}, "
                     f"not 'tpu'; the benchmark has no CPU fallback")
    if len(devices) != chips:
        raise NoChip(f"bench: the cell needs {chips} TPU devices, JAX finds "
                     f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``.

    The path is fixed, since it is part of the cache's key; every program is
    kept, however fast it compiled.
    """
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json; add its published peaks")
    return table[kind]


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, where reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class RunContext:
    """What a driver is given, and what the metric readers read afterwards."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    traced: bool
    started: float                      # perf_counter at process start
    device: dict | None = None          # None: no chip check (tests)
    config_code: object = None
    counters: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None           # reduced profile of the traced span
    peaks: dict | None = None
    checked: object = None              # what the check compared (control.py)


@dataclasses.dataclass
class DriverResult:
    """A driver's outcome; ``metrics`` are the end-to-end values it timed."""

    attempted: int
    failed: int
    metrics: dict                       # name -> value
    checks: dict                        # name -> {"value": v, "limit": l}
    memory_peak_bytes: int | None


def checks_pass(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def read_per_layer(bench: dict, ctx: RunContext) -> dict:
    """Run every per-layer reader of the cell; leave out what reads nothing."""
    out = {}
    for entry in metrics_for(bench, ctx.cell, traced=True):
        mod = load_module(BENCH_DIR / "metrics" / f"{entry['name']}.py",
                          f"bench_metric_{entry['name']}")
        value = mod.read(ctx)
        if value is None:
            print(f"bench: per-layer metric {entry['name']} found nothing to "
                  f"read in this run and is left out", file=sys.stderr)
            continue
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def execute(bench: dict, ctx: RunContext) -> dict:
    """Drive one run of a cell; return the result object to print."""
    driver = importlib.import_module(f"bench.drivers.{ctx.mix['driver']}")
    res: DriverResult = driver.run(ctx)
    if ctx.traced:
        metrics = read_per_layer(bench, ctx)
    else:
        units = {m["name"]: m["unit"]
                 for m in metrics_for(bench, ctx.cell, traced=False)}
        metrics = {name: {"value": res.metrics[name], "unit": unit}
                   for name, unit in units.items()}
    device = dict(ctx.device or {"platform": "none", "kind": "none",
                                 "count": 0})
    device["memory_peak_bytes"] = res.memory_peak_bytes
    out = {"correct": res.failed == 0 and checks_pass(res.checks),
           "attempted": res.attempted, "failed": res.failed,
           "metrics": metrics, "device": device}
    if ctx.traced and ctx.trace is not None and ctx.trace["devices"]:
        from bench import tracing

        device["busy_s"] = tracing.busy_s(ctx.trace)
        device["window_s"] = tracing.window_s(ctx.trace)
        out["breakdown"] = tracing.breakdown(ctx.trace)
    out["checks"] = res.checks
    return out


def report_checks(checks: dict) -> None:
    """Each compared number beside its limit, as the last lines on stderr."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] is not None and c["value"] <= c["limit"] \
            else "FAILED"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
