"""The port-limited plan cell (``mems256.plan-miss``) on the CPU at small
sizes: its plain reference of the blocked ring, a rehearsal of its driver,
its control, and the faults that ``correct`` must catch when planted under
the timed path."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, traffic  # noqa: E402
from bench.reference import blocked, fabric  # noqa: E402

BENCH = harness.load_benchmark()
CELL = "mems256.plan-miss"
LARGE_SEED = 2**31 + 54321
FAB = {"alpha_s": 1.7e-6, "alpha_h": 1e-6, "bandwidth": 1e11, "delta": 2e-5}


def _schedules(kind, n):
    from itertools import product

    S = n.bit_length() - 1
    return [(0,) + t for t in product((0, 1), repeat=S - 1)]


@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_reference_on_blocks_of_one_is_the_full_port_reference(kind):
    for x in _schedules(kind, 16):
        assert blocked.completion(kind, 16, x, 3e6, FAB, 4, 1) == \
            fabric.completion(kind, 16, x, 3e6, FAB, 4)


@pytest.mark.parametrize("n,ports", [(16, 16), (16, 8), (32, 32)])
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_reference_agrees_with_the_event_oracle(kind, n, ports):
    from repro.core.cost_model import CostModel
    from repro.core.fabricsim import FabricSim
    from repro.core.schedules import Schedule

    cm = CostModel(alpha_s=FAB["alpha_s"], alpha_h=FAB["alpha_h"],
                   bandwidth=FAB["bandwidth"], delta=FAB["delta"])
    b = blocked.block_size(n, ports)
    sim = FabricSim(chunks_per_msg=4, ports=ports)
    for x in _schedules(kind, n):
        want = sim.run(Schedule(kind=kind, n=n, x=x), 3e6, cm).completion
        got = blocked.completion(kind, n, x, 3e6, FAB, 4, b)
        assert got == pytest.approx(want, rel=1e-12), x


def test_reference_hops_are_the_block_floor():
    # n = 16, blocks of 2: a circuit of offset 8 takes 2 hops, offset 2 = B
    # shortens nothing (the static ring), offset 1 is the static ring
    assert [blocked.hops(8, g, 2) for g in (1, 2, 4, 8)] == [8, 8, 4, 2]
    succ = blocked.successors(8, 4, 2)
    assert succ.tolist() == [1, 4, 3, 6, 5, 0, 7, 2]
    with pytest.raises(ValueError):
        blocked.block_size(16, 5)


def _context(seed, seconds=1.0, n=32, ports=32):
    cell = harness.find_cell(BENCH, CELL)
    cfg = harness.load_config(cell["config"])
    cfg["n"], cfg["ports"] = n, ports
    mix = traffic.load_mix(cell["traffic"])
    mix["warmup"] = [["a2a", 3.0e6]]
    mix["check_sample"] = 6
    return harness.RunContext(
        cell=cell, config=cfg, mix=mix, seed=seed, seconds=seconds,
        traced=False, started=time.perf_counter(),
        peaks=harness.peaks_for("TPU v5 lite"))


def test_cell_names_its_driver_config_and_metrics():
    cell = harness.find_cell(BENCH, CELL)
    cfg = harness.load_config(cell["config"])
    assert (cfg["n"], cfg["ports"], cfg["fabric"]) == (256, 320, "ocs-sim")
    assert blocked.block_size(cfg["n"], cfg["ports"]) == 2
    assert traffic.load_mix(cell["traffic"])["driver"] == \
        "plan_closed_loop_ports"
    assert {m["name"] for m in harness.metrics_for(BENCH, cell, True)} == {
        "device_lanes_per_plan.ports", "playback_ms_per_plan.ports",
        "device_idle_share.ports", "blocked_hop_share",
        "playback_compiles.ports"}
    assert {m["name"] for m in harness.metrics_for(BENCH, cell, False)} == {
        "setup_s", "plans_per_s", "plan_ms_p90"}


def test_cell_rehearsal_is_correct_and_counts_its_hops():
    ctx = _context(LARGE_SEED)
    out = harness.execute(BENCH, ctx)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "plans_per_s", "plan_ms_p90"}
    assert out["attempted"] == ctx.counters["plans"] > 0
    assert {"lanes", "compiles", "hops", "blocked_hops"} <= set(ctx.counters)
    assert all(res.request.ports == 32 for _, _, res in ctx.checked)


def test_cell_rehearsal_on_the_device_path_reads_its_share(monkeypatch):
    """With the device chosen (as on the chip), the playback kernel plays
    the blocked sets and the counter readers find something."""
    from repro.core import batchsim

    monkeypatch.setattr(batchsim, "_JAX_AUTO_MIN_WORK", 0.0)
    ctx = _context(7)
    out = harness.execute(BENCH, ctx)
    assert out["correct"], out["checks"]
    assert ctx.counters["lanes"] > 0 and ctx.counters["blocked_hops"] > 0
    share = harness.load_module(ROOT / "bench" / "metrics" /
                                "blocked_hop_share.py", "t_share").read(ctx)
    lanes = harness.load_module(
        ROOT / "bench" / "metrics" / "device_lanes_per_plan.ports.py",
        "t_lanes").read(ctx)
    compiles = harness.load_module(
        ROOT / "bench" / "metrics" / "playback_compiles.ports.py",
        "t_compiles").read(ctx)
    assert 0.0 < share < 1.0 and lanes > 0
    # the warm-up grid compiled every bucket shape the window met
    assert compiles == 0


def test_blocked_hop_share_reads_nothing_without_its_counters():
    ctx = _context(1)
    ctx.counters.update(plans=10, lanes=20)
    mod = harness.load_module(ROOT / "bench" / "metrics" /
                              "blocked_hop_share.py", "t_share2")
    assert mod.read(ctx) is None


def test_control_comes_out_not_correct():
    ctx = _context(5)
    harness.execute(BENCH, ctx)
    numbers, _ = blocked.compare(
        ctx.checked, ctx.config["n"], {
            "alpha_s": ctx.config["alpha_s"], "alpha_h": ctx.config["alpha_h"],
            "bandwidth": ctx.config["link_gbps"] * 1e9 / 8.0,
            "delta": ctx.config["delta"]},
        ctx.config["planner"]["sim_chunks"], ctx.config["ports"],
        rank_tol=ctx.mix["limits"]["score_rel_gap"],
        control_dtype=np.float32)
    assert numbers["score_rel_gap"] > ctx.mix["limits"]["score_rel_gap"]


def _fault_full_port(schedules, m, cm, ports=None, **kw):
    from repro.core.batchsim import batch_completion_times
    return batch_completion_times(schedules, m, cm, **kw)


def _fault_dropped_lane(schedules, m, cm, **kw):
    from repro.core.batchsim import batch_completion_times
    head = batch_completion_times(schedules[:-1], m, cm, **kw)
    return np.concatenate([head, head[:1]])


@pytest.mark.parametrize("verify", [True, False],
                         ids=["verifier_on", "reference_alone"])
@pytest.mark.parametrize("fault", ["wrong_block", "full_port", "dropped_lane"])
def test_cell_faults_come_out_not_correct(fault, verify, monkeypatch):
    """Each fault fails the planner's own verifier (plan/block), and with
    the verifier off the reference's score gap still catches it."""
    from repro.core import batchsim
    from repro.planner import planner as planner_mod

    if fault == "wrong_block":
        real = batchsim.block_size
        monkeypatch.setattr(batchsim, "block_size",
                            lambda n, ports: 2 * real(n, ports))
    elif fault == "full_port":
        monkeypatch.setattr(planner_mod, "batch_completion_times",
                            _fault_full_port)
    else:
        monkeypatch.setattr(planner_mod, "batch_completion_times",
                            _fault_dropped_lane)
    ctx = _context(3)
    ctx.mix["warmup"] = []       # planted under the timed path only
    ctx.config["planner"]["verify"] = verify
    out = harness.execute(BENCH, ctx)
    assert not out["correct"], out["checks"]
    caught = "failed_plans" if verify else "score_rel_gap"
    assert out["checks"][caught]["value"] > out["checks"][caught]["limit"]
