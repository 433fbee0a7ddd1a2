"""Run one cell of the benchmark once and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its traffic
mix are found by name through ``BENCHMARK.json``. The run refuses, exits
non-zero and prints no result unless JAX finds exactly the cell's number of
TPU chips. It warms up every shape the window uses (counted in ``setup_s``),
measures for ``--seconds``, then checks what the window produced against a
plain reference. ``--trace 1`` profiles the window and reports the cell's
per-layer metrics in place of its end-to-end ones. The last line of standard
output is the result; the last lines of standard error are the compared
numbers beside their limits.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# this directory must not shadow modules of the standard library or of JAX
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test is missing ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    from bench import harness, traffic

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    device = harness.device_check(int(cell["chips"]))
    harness.enable_compile_cache()
    ctx = harness.RunContext(
        cell=cell, config=harness.load_config(cell["config"]),
        mix=traffic.load_mix(cell["traffic"]), seed=args.seed,
        seconds=args.seconds, traced=bool(args.trace), started=STARTED,
        device=device, config_code=harness.config_module(cell["config"]),
        peaks=harness.peaks_for(device["kind"]))
    out = harness.execute(bench, ctx)
    harness.report_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
