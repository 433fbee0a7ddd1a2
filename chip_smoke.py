"""Chip smoke: drive both device paths of the planner once on a TPU.

    python chip_smoke.py               # one chip: device check, plan, scoring
    python chip_smoke.py --four-chips  # four chips: planned collectives only

Phases, each printed as one line before the last:

  device   ``jax.devices()[0].platform`` must be ``tpu``.  There is no CPU
           fallback: without a TPU the script exits non-zero and prints no
           result.
  plan     Event-scored planning through the normal entry point:
           ``Planner(sim_backend="jax")`` plans a2a / rs / ag / ar at n=512 on
           the paper's Table 2 Piezo OCS (25 ms, 576 ports) with 16 MiB per
           node.  The same requests planned with ``sim_backend="numpy"`` in
           this process are the reference: the device's winner must be
           NumPy's or tie with it within 1e-9 relative on NumPy's scores,
           every alternative's score must be within 1e-6 relative, and the
           playback kernel's call count must rise for every request.
  scoring  ``batch_run(backend="jax")`` on the sim_bench JAX tier's shape
           (256 certified lanes, n=1536, C=4, hop cap 300) against
           ``backend="numpy"``: every lane certified and played on the
           device, worst relative difference <= 1e-6, two device runs
           bit-equal.
  four-chip (``--four-chips`` only, needs exactly 4 TPU devices) the Bruck
           collectives under ``shard_map`` on a mesh of all four devices
           against XLA's own: ``bruck_all_to_all`` vs ``all_to_all`` on an
           MoE dispatch buffer at the published d_model of
           qwen3-moe-235b-a22b (exactly equal), planned-schedule
           ``bruck_reduce_scatter`` / ``bruck_all_gather`` vs
           ``psum_scatter`` / ``all_gather``, and ``bridge_all_reduce`` vs
           ``psum`` on a 2^26-element float32 gradient bucket per chip
           (1e-5 relative for summation order).

Times printed are wall seconds of single calls (chip smoke, not a
benchmark).  Any failed check raises and the script exits non-zero; the
last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process, NumPy references included.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
MiB = 1024.0 ** 2
NOT_A_BENCHMARK = "(chip smoke, not a benchmark)"
AXIS = "x"

PLAN_KINDS = ("a2a", "rs", "ag", "ar")
PLAN_TECH = "piezo_polatis"     # paper Table 2: 25 ms, 576 ports
PLAN_N = 512
PLAN_M_BYTES = 16 * MiB
SCORE_TOL = 1e-6                # alternative scores, device vs NumPy
TIE_TOL = 1e-9                  # a different winner must tie on NumPy's scores
PLAYBACK_TOL = 1e-6             # scoring phase, device vs NumPy
ALLREDUCE_TOL = 1e-5            # float32 summation order

MOE_CONFIG = "qwen3-moe-235b-a22b"
MOE_TOKENS = 4096               # tokens per chip dispatched, each to top_k experts
GRAD_ELEMS = 1 << 26            # float32 gradient bucket per chip


class SmokeError(RuntimeError):
    """A phase's check failed."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def _rel_diff(got, want) -> float:
    """Worst elementwise |got - want| / |want| (0 for empty inputs)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), np.finfo(np.float64).tiny)))


def device_check(count: int | None = None) -> dict:
    """The device JAX reports; refuse anything but a TPU (and, when
    ``count`` is given, anything but exactly that many devices)."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip smoke: jax.devices()[0].platform is {d0.platform!r}, not "
            f"'tpu'; this script has no CPU fallback")
    if count is not None and len(devices) != count:
        raise SystemExit(
            f"chip smoke: needs {count} TPU devices, found {len(devices)}")
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# --- path 1: event-scored planning -------------------------------------------


def _key(alt) -> tuple:
    return alt.strategy, alt.impl, alt.R, alt.x


def _alternative_scores(res) -> dict:
    return {_key(a): a.score for a in res.alternatives}


def plan_phase(n: int = PLAN_N, m_bytes: float = PLAN_M_BYTES,
               kinds=PLAN_KINDS):
    """Plan every kind on the device and with NumPy; yield one line each."""
    from repro.core import batchsim_jax
    from repro.core.cost_model import ocs_preset
    from repro.planner import FabricKind, Planner, PlanRequest

    cm = ocs_preset(PLAN_TECH)
    # no plan cache: the repeat must reach the device again
    device = Planner(sim_backend="jax", cache_size=0)
    reference = Planner(sim_backend="numpy", cache_size=0)
    for kind in kinds:
        req = PlanRequest(kind=kind, n=n, r=2, m_bytes=m_bytes,
                          fabric=FabricKind.OCS_SIM, cost_model=cm)
        before = batchsim_jax.compile_stats()
        t0 = time.perf_counter()
        cold = device.plan(req)
        t1 = time.perf_counter()
        warm = device.plan(req)
        t2 = time.perf_counter()
        mid = batchsim_jax.compile_stats()
        want = reference.plan(req)
        after = batchsim_jax.compile_stats()

        calls = mid["calls"] - before["calls"]
        _require(calls >= 2, f"plan {kind}: playback kernel calls rose by "
                             f"{calls} over two device plans (expected >= 2)")
        _require(after["calls"] == mid["calls"],
                 f"plan {kind}: the NumPy planner reached the device kernel")
        got_s, warm_s, want_s = (_alternative_scores(r)
                                 for r in (cold, warm, want))
        _require(got_s.keys() == want_s.keys(),
                 f"plan {kind}: device and NumPy scored different "
                 f"alternatives")
        _require(got_s == warm_s, f"plan {kind}: repeat device plan differs")
        worst = max(_rel_diff(got_s[k], want_s[k]) for k in want_s)
        _require(worst <= SCORE_TOL,
                 f"plan {kind}: worst alternative score differs by {worst} "
                 f"relative (> {SCORE_TOL})")
        best = want.alternatives[0].score
        won = want_s[_key(cold.alternatives[0])]
        _require(cold.strategy == want.strategy
                 or abs(won - best) <= TIE_TOL * abs(best),
                 f"plan {kind}: device winner {cold.strategy} scores {won} "
                 f"on NumPy, NumPy winner {want.strategy} scores {best}")
        yield (
            f"plan {kind}: n={n} m_bytes={m_bytes} tech={PLAN_TECH} "
            f"alternatives={len(want.alternatives)} "
            f"certified_lanes_on_device={(mid['lanes'] - before['lanes']) // 2} "
            f"playback_calls={calls // 2} "
            f"kernel_calls={(mid['buckets'] - before['buckets']) // 2} "
            f"winner={cold.strategy} "
            f"numpy_winner={want.strategy} worst_rel_diff={worst!r} "
            f"cold_s={t1 - t0!r} warm_s={t2 - t1!r} {NOT_A_BENCHMARK}")


def scoring_phase(n: int = 1536, lanes_target: int = 256, chunks: int = 4,
                  hop_cap: int = 300, m_bytes: float = 4 * MiB) -> str:
    """sim_bench's JAX tier through `batch_run`, device vs NumPy."""
    from benchmarks.sim_bench import DELTA, _jax_lanes
    from repro.core import PAPER_DEFAULT
    from repro.core.batchsim import batch_run

    cm = PAPER_DEFAULT.replace(delta=DELTA)
    lanes = _jax_lanes(n, m_bytes, lanes_target=lanes_target, hop_cap=hop_cap)

    def run(backend):
        return batch_run(lanes, cm, chunks_per_msg=chunks, certify=True,
                         backend=backend)

    t0 = time.perf_counter()
    first = run("jax")
    t1 = time.perf_counter()
    second = run("jax")
    t2 = time.perf_counter()
    want = run("numpy")

    _require(first.backend == "jax" and second.backend == "jax",
             f"scoring: backend {first.backend!r}, expected 'jax'")
    certified = int(first.certified.sum())
    _require(certified == len(lanes),
             f"scoring: {certified}/{len(lanes)} lanes certified")
    worst = max(_rel_diff(first.node_done, want.node_done),
                _rel_diff(first.step_done, want.step_done))
    _require(worst <= PLAYBACK_TOL,
             f"scoring: worst relative difference {worst} > {PLAYBACK_TOL}")
    _require(np.array_equal(first.node_done, second.node_done)
             and np.array_equal(first.step_done, second.step_done),
             "scoring: two device runs are not bit-equal")
    return (f"scoring: n={n} C={chunks} hop_cap={hop_cap} lanes={len(lanes)} "
            f"certified_lanes={certified} backend={first.backend} "
            f"worst_rel_diff={worst!r} bit_stable=True "
            f"cold_s={t1 - t0!r} warm_s={t2 - t1!r} {NOT_A_BENCHMARK}")


# --- path 2: planned collectives on a mesh ------------------------------------


def moe_dispatch_rows(n_chips: int) -> tuple[int, int]:
    """(rows per destination chip, d_model) of one MoE dispatch a2a."""
    from repro import configs

    cfg = configs.get(MOE_CONFIG)
    return MOE_TOKENS * cfg.moe.top_k // n_chips, cfg.d_model


def collective_cases(mesh, *, a2a_rows: int, d_model: int,
                     grad_elems: int = GRAD_ELEMS) -> list[dict]:
    """The four-chip phase's programs on ``mesh`` (1-D, axis ``AXIS``).

    Each case holds ``planned`` and ``xla`` (jitted ``shard_map`` programs
    of one global input), that input's ``ShapeDtypeStruct`` sharded over the
    mesh, and the relative tolerance (0 = exactly equal).  Shapes only: the
    same cases compile for a described topology that holds no arrays.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.collectives import (bridge_all_reduce, bruck_all_gather,
                                   bruck_all_to_all, bruck_reduce_scatter)
    from repro.core.cost_model import TPU_V5E
    from repro.planner import Planner, PlanRequest

    n = mesh.devices.size
    chunk = grad_elems // n
    planner = Planner()
    rs = planner.plan(PlanRequest(kind="rs", n=n, m_bytes=4.0 * grad_elems,
                                  cost_model=TPU_V5E)).schedule
    ag = planner.plan(PlanRequest(kind="ag", n=n, m_bytes=4.0 * grad_elems,
                                  cost_model=TPU_V5E)).schedule

    def program(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(AXIS),
                                     out_specs=P(AXIS)))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(AXIS)))

    return [
        {"name": "all_to_all", "tol": 0.0,
         "what": f"per-chip ({n}, {a2a_rows}, {d_model}) bfloat16",
         "planned": program(lambda x: bruck_all_to_all(x, AXIS)),
         "xla": program(lambda x: jax.lax.all_to_all(x, AXIS, 0, 0)),
         "arg": arg((n * n, a2a_rows, d_model), jnp.bfloat16)},
        {"name": "reduce_scatter", "tol": ALLREDUCE_TOL,
         "what": f"per-chip ({n}, {chunk}) float32, schedule x={rs.x}",
         "planned": program(lambda x: bruck_reduce_scatter(x, AXIS, rs)),
         "xla": program(lambda x: jax.lax.psum_scatter(x, AXIS)),
         "arg": arg((n * n, chunk), jnp.float32)},
        {"name": "all_gather", "tol": 0.0,
         "what": f"per-chip ({chunk},) float32, schedule x={ag.x}",
         "planned": program(lambda x: bruck_all_gather(x, AXIS, ag)),
         "xla": program(lambda x: jax.lax.all_gather(x, AXIS)),
         "arg": arg((n * chunk,), jnp.float32)},
        {"name": "all_reduce", "tol": ALLREDUCE_TOL,
         "what": f"per-chip ({grad_elems},) float32",
         "planned": program(lambda x: bridge_all_reduce(x, AXIS, n)),
         "xla": program(lambda x: jax.lax.psum(x, AXIS)),
         "arg": arg((n * grad_elems,), jnp.float32)},
    ]


def four_chip_phase(devices, *, a2a_rows: int | None = None,
                    d_model: int | None = None,
                    grad_elems: int = GRAD_ELEMS):
    """Run each planned collective and XLA's own on a mesh of ``devices``;
    yield one line each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices), (AXIS,))
    if a2a_rows is None or d_model is None:
        a2a_rows, d_model = moe_dispatch_rows(len(devices))

    @jax.jit
    def compare(got, want):
        got = got.astype(jnp.float32)
        want = want.astype(jnp.float32)
        return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))

    def timed(fn, x):
        t0 = time.perf_counter()
        out = fn(x).block_until_ready()
        return out, time.perf_counter() - t0

    for seed, case in enumerate(collective_cases(
            mesh, a2a_rows=a2a_rows, d_model=d_model, grad_elems=grad_elems)):
        spec = case["arg"]
        x = jax.jit(lambda key, s=spec: jax.random.normal(key, s.shape, s.dtype),
                    out_shardings=spec.sharding)(jax.random.key(seed))
        got, planned_cold = timed(case["planned"], x)
        _, planned_warm = timed(case["planned"], x)
        want, xla_cold = timed(case["xla"], x)
        _, xla_warm = timed(case["xla"], x)
        _require(got.shape == want.shape and got.dtype == want.dtype,
                 f"four-chip {case['name']}: {got.shape} {got.dtype} vs XLA "
                 f"{want.shape} {want.dtype}")
        max_abs, scale = (float(v) for v in compare(got, want))
        rel = max_abs / scale if scale else max_abs
        exact = case["tol"] == 0.0
        _require(max_abs == 0.0 if exact else rel <= case["tol"],
                 f"four-chip {case['name']}: max |planned - xla| = {max_abs} "
                 f"(relative {rel}, allowed {case['tol']})")
        permutes = case["planned"].lower(spec).as_text().count(
            "collective_permute")
        yield (
            f"four-chip {case['name']}: devices={len(devices)} "
            f"{case['what']} ppermutes={permutes} max_abs_diff={max_abs!r} "
            f"rel_diff={rel!r} planned_cold_s={planned_cold!r} "
            f"planned_warm_s={planned_warm!r} xla_cold_s={xla_cold!r} "
            f"xla_warm_s={xla_warm!r} {NOT_A_BENCHMARK}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the planned collectives on 4 TPU devices")
    args = ap.parse_args(argv)

    device = device_check(4 if args.four_chips else None)
    for path in (ROOT, ROOT / "src"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    from benchmarks.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        import jax

        for line in four_chip_phase(jax.devices()):
            print(line, flush=True)
    else:
        for line in plan_phase():
            print(line, flush=True)
        print(scoring_phase(), flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
