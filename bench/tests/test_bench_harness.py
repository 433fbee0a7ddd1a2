"""The benchmark's own tests, on the CPU at small sizes.

They cover the traffic generator, the trace reductions on recorded traces,
the names in BENCHMARK.json, a rehearsal of each cell's driver (the plan
cell at n = 64, the four-chip cell on 4 virtual CPU devices in a child
process), the controls that must come out not correct, and the faults that
``correct`` must catch when planted under the timed path.
"""
from __future__ import annotations

import gzip
import json
import math
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, tracing, traffic  # noqa: E402
from bench.reference import fabric  # noqa: E402

BENCH = harness.load_benchmark()
PLAN_CELL = "paper1024.plan-miss"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LARGE_SEED = 2**31 + 12345


# --- traffic ------------------------------------------------------------------


def _plan_mix():
    return traffic.load_mix(harness.find_cell(BENCH, PLAN_CELL)["traffic"])


def test_plan_requests_repeat_for_a_seed_and_differ_in_order_across_seeds():
    mix = _plan_mix()
    a = traffic.plan_requests(mix, LARGE_SEED)
    b = traffic.plan_requests(mix, LARGE_SEED + 1)
    assert a == traffic.plan_requests(mix, LARGE_SEED)
    assert a != b
    assert len(a) == mix["requests"]
    # the same work in every round, in another order
    kinds = len(mix["kinds"])
    for start in range(0, 200, kinds):
        assert sorted(a[start:start + kinds]) == sorted(b[start:start + kinds])


def test_spread_order_is_a_permutation_spread_from_the_start():
    assert traffic.spread_order(8) == [0, 4, 2, 6, 1, 5, 3, 7]
    for n in (1, 3, 5, 8, 13):
        assert sorted(traffic.spread_order(n)) == list(range(n))


@pytest.mark.parametrize("seed", [0, 7, LARGE_SEED, 2**40 + 3])
def test_plan_requests_all_miss_and_cover_the_mix(seed):
    mix = _plan_mix()
    reqs = traffic.plan_requests(mix, seed)
    sizes = [m for _, m in reqs]
    warm = {m for _, m in mix["warmup"]}
    assert len(set(sizes)) == len(sizes)
    assert not warm & set(sizes)
    assert min(sizes) >= mix["m_bytes_min"] and max(sizes) <= mix["m_bytes_max"]
    # every block of kinds x strata holds each (kind, stratum) pair once,
    # and every round of len(kinds) requests holds each kind once
    kinds = len(mix["kinds"])
    block = kinds * mix["strata"]
    lo, hi = math.log(mix["m_bytes_min"]), math.log(mix["m_bytes_max"])
    for start in range(0, block * 4, block):
        cells = {(k, int((math.log(m) - lo) / (hi - lo) * mix["strata"]))
                 for k, m in reqs[start:start + block]}
        assert len(cells) == block
    for start in range(0, block * 4, kinds):
        assert len({k for k, _ in reqs[start:start + kinds]}) == kinds


def test_check_sample_is_drawn_from_the_seed():
    a = traffic.check_sample(200, 16, LARGE_SEED)
    assert a == traffic.check_sample(200, 16, LARGE_SEED)
    assert len(set(a)) == 16 and all(0 <= i < 200 for i in a)
    assert traffic.check_sample(5, 16, 1) == [0, 1, 2, 3, 4]


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        traffic.rng(-1, "x")


# --- trace reductions -----------------------------------------------------------


def _hand_trace():
    """Two devices, window 0..100 ns, with busy unions of 55 and 40 ns."""
    return {
        "window": [0.0, 100.0],
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [["fusion.1", 10.0, 20.0], ["fusion.2", 15.0, 10.0],
                     ["while.3", 70.0, 40.0], ["copy", -5.0, 10.0]],
             "modules": [["jit_bench_x(7)", 10.0, 30.0],
                         ["jit_bench_x(7)", 70.0, 30.0],
                         ["jit_bench_x_reference(8)", 40.0, 5.0]]},
            {"name": "/device:TPU:1",
             "ops": [["fusion.9", 0.0, 40.0]],
             "modules": [["jit_bench_x(3)", 0.0, 40.0]]}],
        "host_spans": [["bench.step_dispatch", 35.0, 50.0],
                       ["bench.plan", 30.0, 70.0]]}


def test_busy_and_idle_share_are_unions_clipped_to_the_window():
    t = _hand_trace()
    # device 0: [0,5] + [10,30] + [70,100] = 55 ns; device 1: 40 ns
    assert tracing.window_s(t) == pytest.approx(100e-9)
    assert tracing.busy_s(t) == pytest.approx((55 + 40) / 2 * 1e-9)
    assert tracing.idle_share(t) == pytest.approx(1 - 47.5 / 100)


def test_program_seconds_finds_programs_by_name_only():
    t = _hand_trace()
    secs, calls = tracing.program_seconds(t, "bench_x")
    assert calls == pytest.approx(1.5)          # 2 calls and 1 call
    assert secs == pytest.approx((60 + 40) / 2 * 1e-9)
    with pytest.raises(tracing.MissingEvent):
        tracing.program_seconds(t, "bench_y")


def test_breakdown_puts_gaps_down_to_the_shortest_covering_span():
    t = _hand_trace()
    b = tracing.breakdown(t)
    ops = dict(b["device_ops"])
    # fusion.2 lies inside fusion.1 on device 0: its 10 ns are not fusion.1's
    assert ops["fusion"] == pytest.approx((10 + 10 + 40) / 2 * 1e-9)
    assert ops["while"] == pytest.approx(40 / 2 * 1e-9)
    gaps = dict(b["idle_gaps"])
    # device 0 gaps: [5,10] (no span), [30,70] (mid 50: step_dispatch, the
    # shorter of the two spans over it); device 1 gap: [40,100] (mid 70)
    assert gaps["bench.step_dispatch"] == pytest.approx((40 + 60) / 2 * 1e-9)
    assert gaps["(no bench span)"] == pytest.approx(5 / 2 * 1e-9)
    assert len(b["device_ops"]) <= tracing.TOP


def test_ici_share_arithmetic_from_shapes():
    t = _hand_trace()
    ctx = _metric_context(t, chips=4, a2a_bytes_per_chip=4e6,
                          allreduce_bytes_per_chip=2e6)
    t["devices"][0]["modules"] = [["jit_bench_moe_a2a(1)", 0.0, 20000.0],
                                  ["jit_bench_moe_allreduce(2)", 0.0, 30000.0]]
    t["devices"][1]["modules"] = [["jit_bench_moe_a2a(1)", 0.0, 20000.0],
                                  ["jit_bench_moe_allreduce(2)", 0.0, 30000.0]]
    peak = ctx.peaks["ici_bits_per_s"] / 8
    assert _read("a2a_ici_share", ctx) == pytest.approx(
        0.75 * 4e6 / peak / 20e-6)
    assert _read("allreduce_ici_share", ctx) == pytest.approx(
        1.5 * 2e6 / peak / 30e-6)


def test_readers_read_nothing_where_their_program_is_missing():
    ctx = _metric_context(_hand_trace(), chips=4, a2a_bytes_per_chip=4e6,
                          allreduce_bytes_per_chip=2e6, traced_plans=3)
    assert _read("a2a_ici_share", ctx) is None
    assert _read("playback_ms_per_plan", ctx) is None
    ctx.trace = None
    assert _read("device_idle_share.plan", ctx) is None


def _recorded(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("name,programs", [
    ("trace_plan_tpu.json.gz", ["play"]),
    ("trace_step_tpu.json.gz", ["bench_moe_a2a", "bench_moe_allreduce"])])
def test_recorded_chip_traces_reduce(name, programs):
    t = _recorded(name)
    assert t["devices"] and all(d["ops"] for d in t["devices"])
    assert 0.0 < tracing.busy_s(t) <= tracing.window_s(t)
    for prog in programs:
        secs, calls = tracing.program_seconds(t, prog)
        assert secs > 0 and calls >= 1
    b = tracing.breakdown(t)
    assert b["device_ops"] and b["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        tracing.window_s(t) - tracing.busy_s(t), rel=1e-9)


def test_recorded_step_trace_ici_shares_stay_under_the_peak():
    t = _recorded("trace_step_tpu.json.gz")
    meta = t["meta"]
    ctx = _metric_context(t, **meta["counters"])
    for name in ("a2a_ici_share", "allreduce_ici_share"):
        assert 0.0 < _read(name, ctx) <= 1.05


def _metric_context(trace, **counters):
    ctx = harness.RunContext(
        cell={}, config={}, mix={}, seed=0, seconds=1.0,
        traced=True, started=0.0, peaks=harness.peaks_for("TPU v5 lite"))
    ctx.trace = trace
    ctx.counters.update(counters)
    return ctx


def _read(metric, ctx):
    mod = harness.load_module(ROOT / "bench" / "metrics" / f"{metric}.py",
                              f"test_metric_{metric}")
    return mod.read(ctx)


# --- BENCHMARK.json -------------------------------------------------------------


def test_benchmark_names_units_and_files():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in b[key]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in b["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    assert b["command"][1] == "bench/run.py" and b["paths"] == ["bench"]


def test_peaks_table_refuses_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["ici_bits_per_s"] == 1.6e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v0 imaginary")


def test_moe_config_states_the_replicated_gradient_count():
    cfg = harness.load_config("qwen3-235b-ep4")
    code = harness.config_module("qwen3-235b-ep4")
    assert code.replicated_grad_elems(cfg) == \
        cfg["deployment"]["replicated_grad_elems"]


# --- the plain reference ----------------------------------------------------------


def test_reference_matches_the_closed_form_of_one_hop_steps():
    """Every-step schedule at n=4, one chunk: each step is one hop. Step 1
    starts when the port is past its rewiring stall and the node has
    received step 0, whichever is later."""
    a_s, a_h, delta = 1e-6, 2e-6, 5e-6
    fab = {"alpha_s": a_s, "alpha_h": a_h, "bandwidth": 1e9, "delta": delta}
    got = fabric.completion("a2a", 4, (0, 1), 8e3, fab, 1)
    tau = 8e3 * 2 / 4 / 1e9
    start1 = max(a_s + tau + delta, (a_s + tau + a_h) + a_s)
    assert got == pytest.approx(start1 + tau + a_h, rel=1e-12)
    # without the rewiring (static schedule) step 1 takes two hops
    got = fabric.completion("a2a", 4, (0, 0), 8e3, fab, 1)
    inject1 = a_s + tau + a_h + a_s
    assert got == pytest.approx(inject1 + 2 * (tau + a_h), rel=1e-12)


def test_reference_refuses_an_invalid_schedule():
    with pytest.raises(ValueError):
        fabric.link_offsets("a2a", 16, (1, 0, 0, 0))
    with pytest.raises(ValueError):
        fabric.bruck_steps("a2a", 12)


# --- rehearsal of the plan cell on the CPU (n = 64) ---------------------------------


def _plan_context(seed, seconds=1.0):
    cell = harness.find_cell(BENCH, PLAN_CELL)
    cfg = harness.load_config(cell["config"])
    cfg["n"] = 64
    mix = traffic.load_mix(cell["traffic"])
    mix["warmup"] = [["a2a", 3.0e6]]
    mix["check_sample"] = 6
    return harness.RunContext(
        cell=cell, config=cfg, mix=mix,
        seed=seed, seconds=seconds, traced=False,
        started=time.perf_counter(), peaks=harness.peaks_for("TPU v5 lite"))


def test_plan_cell_rehearsal_is_correct_and_reports_its_metrics():
    ctx = _plan_context(LARGE_SEED)
    out = harness.execute(BENCH, ctx)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "plans_per_s", "plan_ms_p90"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] == ctx.counters["plans"] > 0
    assert list(out)[-1] == "checks"


def test_plan_control_comes_out_not_correct():
    from bench import control

    ctx = _plan_context(5)
    harness.execute(BENCH, ctx)
    got = control.plan_control(ctx)
    assert got["score_rel_gap"] > ctx.mix["limits"]["score_rel_gap"]


def _fault_scores_scaled(schedules, m, cm, **kw):
    from repro.core.batchsim import batch_completion_times
    return batch_completion_times(schedules, m, cm, **kw) * (1 + 1e-6)


def _fault_half_batch(schedules, m, cm, **kw):
    from repro.core.batchsim import batch_completion_times
    half = max(1, len(schedules) // 2)
    head = batch_completion_times(schedules[:half], m, cm, **kw)
    return np.concatenate([head, np.resize(head, len(schedules) - half)])


@pytest.mark.parametrize("fault", ["altered", "half_batch", "unchanged"])
def test_plan_cell_faults_come_out_not_correct(fault, monkeypatch):
    from repro.planner import planner as planner_mod

    if fault == "altered":
        monkeypatch.setattr(planner_mod, "batch_completion_times",
                            _fault_scores_scaled)
    elif fault == "half_batch":
        monkeypatch.setattr(planner_mod, "batch_completion_times",
                            _fault_half_batch)
    else:
        plan = planner_mod.Planner.plan
        state = {}

        def stale(self, req):
            # returns the state it had: the first answer, again and again
            if "first" not in state:
                state["first"] = plan(self, req)
            return state["first"]

        monkeypatch.setattr(planner_mod.Planner, "plan", stale)
    out = harness.execute(BENCH, _plan_context(3))
    assert not out["correct"], out["checks"]


# --- rehearsal of the four-chip cell on 4 virtual CPU devices -------------------------


@pytest.fixture(scope="module")
def collective_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "_collective_worker.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_collective_cell_rehearsal_is_correct(collective_runs):
    sound = collective_runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["a2a_max_abs_diff"]["value"] == 0.0
    assert set(sound["metrics"]) == {"setup_s", "step_ms"}


def test_collective_control_comes_out_not_correct(collective_runs):
    sound = collective_runs["sound"]
    for name, value in sound["control"].items():
        assert value > sound["checks"][name]["limit"], name


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered",
                                   "no_exchange"])
def test_collective_cell_faults_come_out_not_correct(collective_runs, fault):
    assert not collective_runs[fault]["correct"], collective_runs[fault]


# --- refusal without the chip ---------------------------------------------------------


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", PLAN_CELL, "--seed", "1",
         "--seconds", "1", *extra], capture_output=True, text=True,
        timeout=300, env=env, cwd=cwd)


def test_run_refuses_without_a_tpu_and_prints_no_result():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "tpu" in proc.stderr


def test_run_refuses_in_a_checkout_without_the_system(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
