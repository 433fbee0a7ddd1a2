"""JAX batch backend (`core.batchsim_jax`) vs the NumPy engine and the
scalar oracle.

Pins the PR's acceptance bar:
  - differential grid over n x r x kind x (m, delta, overlap) lanes,
    including certified and fallback lanes: ``backend="jax"`` matches the
    NumPy batch engine within 1e-6 relative (on this CPU it is bit-exact)
    and the scalar sparse oracle within 1e-9;
  - uncertified lanes in a jax-backend batch still route through the
    guarded NumPy path and, when a guard trips, the scalar oracle;
  - playback is bit-stable run-to-run;
  - the jit cache holds: repeated same-shape batches never retrace the
    kernel (recompilation count stays flat);
  - backend resolution: "auto" falls back to NumPy for small batches,
    ``backend="jax"`` demands ``certify=True``, x64 mode never leaks out
    of the playback call;
  - the planner's ``sim_backend`` knob gives backend-identical plans;
  - a jax-less install still imports the core and degrades cleanly
    (the `collectives._compat` guard).
"""
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from repro.core import PAPER_DEFAULT, periodic_a2a, straggler_speeds
from repro.core.batchsim import (BatchLane, batch_completion_times,
                                 batch_run, compile_tape)
from repro.core.bruck import schedule_length
from repro.core.schedules import Schedule

jax = pytest.importorskip("jax")

from repro.core import batchsim_jax  # noqa: E402  (needs the skip above)

MB = 1024.0 ** 2
REL_TOL = 1e-9
JAX_TOL = 1e-6  # the acceptance spec's jax-vs-numpy bar


def random_schedule(rng: random.Random, kind: str, n: int, r: int = 2) -> Schedule:
    s = schedule_length(kind, n, r)
    x = tuple([0] + [rng.randint(0, 1) for _ in range(s - 1)])
    return Schedule(kind=kind, n=n, x=x, r=r)


def scalar_completion(lane: BatchLane, cm, chunks: int) -> float:
    from repro.core import FabricSim

    sim = FabricSim(
        chunks_per_msg=chunks, overlap=lane.overlap, mode="sparse",
        link_speed=list(lane.link_speed) if lane.link_speed else None)
    eff_cm = cm if lane.delta is None else cm.replace(delta=lane.delta)
    return sim.run(lane.schedule, lane.m_bytes, eff_cm).completion


# --- differential grid: jax == numpy batch == scalar oracle -------------------


@pytest.mark.parametrize("n", [6, 12, 48, 96])
def test_differential_grid_jax_matches_numpy_and_scalar(n):
    """Seeded n x r x kind x (m, delta, overlap) grid, one wide batch per
    (n, r): the JAX backend agrees with the NumPy batch engine within 1e-6
    on every lane (certified ones bit-exactly) and with the scalar oracle
    within 1e-9."""
    rng = random.Random(7000 + n)
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    for r in (2, 3):
        lanes = []
        for kind in ("a2a", "rs", "ag"):   # same S at one (n, r): one batch
            for m_mb, delta, overlap in ((0.25, 1e-6, 0.0), (2.0, 15e-3, 0.5)):
                lanes.append(BatchLane(
                    schedule=random_schedule(rng, kind, n, r),
                    m_bytes=m_mb * MB, delta=delta, overlap=overlap))
        # one uncertified lane: a straggler breaks uniformity, so it must
        # route through the guarded NumPy path inside the jax-backend batch
        lanes.append(BatchLane(
            schedule=lanes[0].schedule, m_bytes=MB,
            link_speed=tuple(straggler_speeds(n, {n // 2: 0.3}))))
        chunks = rng.choice([1, 2, 4])
        res_np = batch_run(lanes, cm, chunks_per_msg=chunks)
        res_j = batch_run(lanes, cm, chunks_per_msg=chunks, backend="jax")
        assert res_j.backend == "jax"
        assert res_j.certified[:-1].all() and not res_j.certified[-1]
        np.testing.assert_allclose(res_j.completion, res_np.completion,
                                   rtol=JAX_TOL)
        np.testing.assert_allclose(res_j.node_done, res_np.node_done,
                                   rtol=JAX_TOL)
        np.testing.assert_allclose(res_j.step_done, res_np.step_done,
                                   rtol=JAX_TOL)
        # certified lanes are bit-exact on CPU (same float ops, same order);
        # the uncertified lane ran the identical NumPy code path
        np.testing.assert_array_equal(res_j.node_done, res_np.node_done)
        for b, lane in enumerate(lanes):
            assert res_j.completion[b] == pytest.approx(
                scalar_completion(lane, cm, chunks), rel=REL_TOL)


def test_severe_straggler_falls_back_to_oracle_under_jax_backend():
    """A guard-tripping lane inside a jax-backend batch still lands on the
    scalar oracle, exactly as under the NumPy backend."""
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [
        BatchLane(schedule=periodic_a2a(n, 2), m_bytes=2 * MB),
        BatchLane(schedule=periodic_a2a(n, 2), m_bytes=2 * MB,
                  link_speed=tuple(straggler_speeds(n, {3: 1e-4}))),
    ]
    res_j = batch_run(lanes, cm, chunks_per_msg=2, backend="jax")
    res_np = batch_run(lanes, cm, chunks_per_msg=2)
    assert res_j.certified.tolist() == [True, False]
    assert not res_j.fast_path[1]          # oracle re-run
    np.testing.assert_array_equal(res_j.node_done, res_np.node_done)
    np.testing.assert_array_equal(res_j.completion, res_np.completion)


def test_jax_playback_is_bit_stable_run_to_run():
    n = 48
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [BatchLane(schedule=periodic_a2a(n, R), m_bytes=(R + 1) * MB)
             for R in range(4)]
    runs = [batch_run(lanes, cm, chunks_per_msg=4, backend="jax")
            for _ in range(3)]
    for later in runs[1:]:
        np.testing.assert_array_equal(runs[0].node_done, later.node_done)
        np.testing.assert_array_equal(runs[0].step_done, later.step_done)
        np.testing.assert_array_equal(runs[0].completion, later.completion)


# --- jit cache ----------------------------------------------------------------


def test_recompilation_count_flat_across_same_shape_batches():
    """Same-shape batches must hit the jit cache: trace_count stays flat
    while the dispatch count keeps climbing."""
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)

    def run(seed):
        lanes = [BatchLane(schedule=periodic_a2a(n, R),
                           m_bytes=(1.0 + 0.1 * seed + 0.01 * R) * MB)
                 for R in range(4)]
        return batch_run(lanes, cm, chunks_per_msg=2, backend="jax")

    run(0)  # warm: compiles this (B, S, n, C) shape if not seen yet
    before = batchsim_jax.compile_stats()
    for seed in range(1, 4):
        run(seed)
    after = batchsim_jax.compile_stats()
    assert after["trace_count"] == before["trace_count"]
    assert after["calls"] == before["calls"] + 3


# --- hop buckets: the cost-chosen partition and the shape ladder ---------------


def static_set_hops(lanes: int, static_at: int, seed: int) -> np.ndarray:
    """Hops shaped like a planner's n = 1024 candidate set: one static lane
    of 2^k hops at step k (1023 in all), the rest 10..134 in all."""
    rng = np.random.default_rng(seed)
    S = 10
    others = []
    while len(others) < lanes - 1:
        row = rng.integers(0, 15, size=S)
        row[0] = 1
        if 10 <= row.sum() <= 134:
            others.append(row)
    others.insert(static_at, 1 << np.arange(S))
    return np.array(others, dtype=np.int64)


def bucket_cost(hops, buckets, n, C):
    return sum(float(batchsim_jax._predicted_seconds(
        batchsim_jax.padded_lanes(b.size), hops[b].max(axis=0).sum(), n, C))
        for b in buckets)


@pytest.mark.parametrize("lanes,static_at", [
    (10, 9), (13, 0), (17, 8), (22, 21), (22, 3)])
def test_partition_plays_the_static_lane_alone_unpadded(lanes, static_at):
    hops = static_set_hops(lanes, static_at, seed=lanes)
    buckets = batchsim_jax.partition(hops, n=1024, C=8)
    assert sorted(np.concatenate(buckets).tolist()) == list(range(lanes))
    alone = [b for b in buckets if static_at in b]
    assert [b.tolist() for b in alone] == [[static_at]]
    assert batchsim_jax.padded_lanes(1) == 1
    assert len(buckets) >= 2
    # and it beats the one bucket the set played as before
    assert bucket_cost(hops, buckets, 1024, 8) < 0.5 * bucket_cost(
        hops, [np.arange(lanes)], 1024, 8)


@pytest.mark.parametrize("lanes,seed", [(3, 0), (5, 1), (7, 2), (8, 3)])
def test_partition_is_the_cheapest_contiguous_split(lanes, seed):
    """Against every contiguous split of the lanes sorted by total hops
    (totals distinct, so every cut point is open to the search)."""
    import itertools

    rng = np.random.default_rng(seed)
    while True:
        hops = rng.integers(0, 60, size=(lanes, 6)).astype(np.int64)
        hops[seed % lanes] = 1 << np.arange(6) * 2
        if len(set(hops.sum(axis=1).tolist())) == lanes:
            break
    n, C = 1024, 8
    order = np.argsort(hops.sum(axis=1), kind="stable")
    best = min(
        bucket_cost(hops, np.split(order, list(cuts)), n, C)
        for k in range(lanes)
        for cuts in itertools.combinations(range(1, lanes), k))
    chosen = batchsim_jax.partition(hops, n, C)
    assert bucket_cost(hops, chosen, n, C) == pytest.approx(best, rel=1e-12)
    assert all((np.diff(hops.sum(axis=1)[b]) > 0).all() for b in chosen)


def test_partition_of_a_wide_batch_beats_four_equal_buckets():
    """256 lanes over 18 distinct totals: the cut points are the 17 run
    boundaries, and the split is at least as cheap as the four equal-count
    buckets that wide batches used to play as."""
    rng = np.random.default_rng(11)
    base = rng.integers(1, 30, size=(18, 11)).astype(np.int64)
    hops = base[rng.integers(0, 18, size=256)]
    n, C = 1536, 4
    buckets = batchsim_jax.partition(hops, n, C)
    assert sorted(np.concatenate(buckets).tolist()) == list(range(256))
    assert len(buckets) <= len(set(hops.sum(axis=1).tolist()))
    order = np.argsort(hops.sum(axis=1), kind="stable")
    for k in (1, 2, 4):
        assert bucket_cost(hops, buckets, n, C) <= bucket_cost(
            hops, np.array_split(order, k), n, C)


def test_padded_lanes_climb_the_ladder():
    lanes = np.arange(1, 301)
    padded = batchsim_jax.padded_lanes(lanes)
    ladder = [1, 2, 4, 8] + list(range(16, 305, 8))
    assert set(padded.tolist()) <= set(ladder)
    assert (padded >= lanes).all()
    # the lowest rung that holds them
    assert all(p == min(r for r in ladder if r >= L)
               for L, p in zip(lanes.tolist(), padded.tolist()))


@pytest.fixture
def split_everything(monkeypatch):
    """A cost model with no per-call or per-trip cost: small-n batches then
    split into several padded buckets, as n = 1024 sets do on the chip."""
    monkeypatch.setattr(batchsim_jax, "_CALL_S", 0.0)
    monkeypatch.setattr(batchsim_jax, "_TRIP_S", 0.0)


def mixed_hop_lanes(n: int) -> list:
    """Lanes of one kind and n with very different hop counts: every R of
    the periodic a2a family, each at three sizes."""
    S = schedule_length("a2a", n, 2)
    return [BatchLane(schedule=periodic_a2a(n, R), m_bytes=(1 + R + 0.5 * j) * MB)
            for R in range(S) for j in range(3)]


@pytest.mark.parametrize("n", [12, 48])
def test_padded_buckets_play_bit_identical_in_lane_order(n, split_everything):
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = mixed_hop_lanes(n)
    random.Random(n).shuffle(lanes)
    hops = np.stack([compile_tape(lane.schedule).hops for lane in lanes])
    buckets = batchsim_jax.partition(hops, n, 4)
    assert len(buckets) >= 2
    assert any(batchsim_jax.padded_lanes(b.size) > b.size for b in buckets)
    res_np = batch_run(lanes, cm, chunks_per_msg=4)
    res_j = batch_run(lanes, cm, chunks_per_msg=4, backend="jax")
    assert res_j.backend == "jax" and res_j.certified.all()
    np.testing.assert_array_equal(res_j.node_done, res_np.node_done)
    np.testing.assert_array_equal(res_j.step_done, res_np.step_done)
    np.testing.assert_array_equal(res_j.completion, res_np.completion)
    for b, lane in enumerate(lanes):
        assert res_j.completion[b] == pytest.approx(
            scalar_completion(lane, cm, 4), rel=REL_TOL)


def test_stats_count_real_lanes_and_kernel_calls(split_everything):
    n = 48
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = mixed_hop_lanes(n)
    hops = np.stack([compile_tape(lane.schedule).hops for lane in lanes])
    buckets = batchsim_jax.partition(hops, n, 4)
    before = batchsim_jax.compile_stats()
    batch_run(lanes, cm, chunks_per_msg=4, backend="jax")
    after = batchsim_jax.compile_stats()
    assert after["calls"] - before["calls"] == 1
    assert after["buckets"] - before["buckets"] == len(buckets) >= 2
    assert after["lanes"] - before["lanes"] == len(lanes)
    assert sum(int(batchsim_jax.padded_lanes(b.size)) for b in buckets) > \
        len(lanes)


def test_padded_shapes_are_on_the_ladder_and_compile_once(monkeypatch,
                                                          split_everything):
    n = 48
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    real = batchsim_jax._kernel()
    shapes = []

    def recording(nb, *args, **kwargs):
        shapes.append(nb.shape)
        return real(nb, *args, **kwargs)

    monkeypatch.setattr(batchsim_jax, "_kernel", lambda: recording)
    ladder = {1, 2, 4} | set(range(8, 257, 8))

    def run(scale):
        lanes = [BatchLane(schedule=lane.schedule, m_bytes=lane.m_bytes * scale)
                 for lane in mixed_hop_lanes(n)]
        return batch_run(lanes, cm, chunks_per_msg=4, backend="jax")

    run(1.0)
    first = list(shapes)
    assert len(first) >= 2 and {L for L, _ in first} <= ladder
    before = batchsim_jax.compile_stats()
    for scale in (1.5, 2.0, 3.0):
        run(scale)
    after = batchsim_jax.compile_stats()
    assert after["trace_count"] == before["trace_count"]
    assert shapes == first * 4


def test_x64_mode_does_not_leak_out_of_playback():
    """`enable_x64` is a context around the playback call only; other jax
    users in the process must still see default float32 semantics."""
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB)]
    res = batch_run(lanes, cm, chunks_per_msg=2, backend="jax")
    assert res.node_done.dtype == np.float64
    assert jax.numpy.zeros(1).dtype == np.float32


# --- backend resolution -------------------------------------------------------


def test_auto_backend_keeps_numpy_for_small_batches():
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB)]
    assert batch_run(lanes, cm, backend="auto").backend == "numpy"


def test_auto_backend_picks_jax_above_the_work_floor(monkeypatch):
    from repro.core import batchsim

    monkeypatch.setattr(batchsim, "_JAX_AUTO_MIN_WORK", 0.0)
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB)]
    res = batch_run(lanes, cm, chunks_per_msg=2, backend="auto")
    assert res.backend == "jax"
    ref = batch_run(lanes, cm, chunks_per_msg=2)
    np.testing.assert_array_equal(res.node_done, ref.node_done)


def test_jax_backend_requires_certify():
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB)]
    with pytest.raises(ValueError, match="certify=True"):
        batch_run(lanes, cm, backend="jax", certify=False)
    # auto quietly degrades instead of raising
    assert batch_run(lanes, cm, backend="auto",
                     certify=False).backend == "numpy"


def test_unknown_backend_rejected():
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB)]
    with pytest.raises(ValueError, match="backend"):
        batch_run(lanes, cm, backend="torch")


def test_all_uncertified_jax_batch_degrades_to_numpy():
    """backend='jax' with zero certified lanes has nothing for the kernel;
    it resolves to the NumPy engine rather than dispatching an empty call."""
    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    speed = tuple(straggler_speeds(n, {0: 0.5}))
    lanes = [BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB,
                       link_speed=speed)]
    res = batch_run(lanes, cm, backend="jax")
    assert res.backend == "numpy"
    assert not res.certified.any()


def test_partition_backends_matches_certificates():
    from repro.analysis.certifier import certify_batch, partition_backends

    n = 12
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    lanes = [
        BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB),
        BatchLane(schedule=periodic_a2a(n, 1), m_bytes=MB,
                  link_speed=tuple(straggler_speeds(n, {0: 0.5}))),
        BatchLane(schedule=periodic_a2a(n, 2), m_bytes=2 * MB),
    ]
    jidx, uidx, mask = partition_backends(lanes, cm)
    np.testing.assert_array_equal(mask, certify_batch(lanes, cm))
    assert jidx.tolist() == [0, 2] and uidx.tolist() == [1]


# --- planner integration ------------------------------------------------------


def test_planner_sim_backend_parity():
    """ocs-sim plans are identical across sim backends — same winner, same
    predicted time (the scores are the same floats)."""
    from repro.planner import Planner, PlanRequest

    cm = PAPER_DEFAULT.replace(delta=1e-3)
    req = PlanRequest(kind="a2a", n=48, m_bytes=2 * MB, cost_model=cm,
                      fabric="ocs-sim")
    res_np = Planner(cache_size=0, sim_backend="numpy").plan(req)
    res_j = Planner(cache_size=0, sim_backend="jax").plan(req)
    assert res_j.schedule.x == res_np.schedule.x
    assert res_j.predicted_time == res_np.predicted_time
    assert [a.score for a in res_j.alternatives] == \
        [a.score for a in res_np.alternatives]


def test_planner_rejects_unknown_sim_backend():
    from repro.planner import Planner

    with pytest.raises(ValueError, match="sim_backend"):
        Planner(sim_backend="cupy")


def test_batch_completion_times_backend_parity():
    n = 48
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    scheds = [periodic_a2a(n, R) for R in range(4)]
    t_np = batch_completion_times(scheds, 2 * MB, cm, chunks_per_msg=4)
    t_j = batch_completion_times(scheds, 2 * MB, cm, chunks_per_msg=4,
                                 backend="jax")
    np.testing.assert_array_equal(t_np, t_j)


# --- jax-less installs (the _compat import guard) -----------------------------


def test_core_imports_and_degrades_without_jax(tmp_path):
    """With jax unimportable, the NumPy core must import and run, 'auto'
    must resolve to numpy, and backend='jax' must raise a clear ImportError
    (the satellite fix: kernels/-style jax probes never leak into the core
    import path)."""
    (tmp_path / "jax.py").write_text("raise ImportError('jax disabled')\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = "\n".join([
        "import numpy as np",
        "from repro.collectives import _compat",
        "assert not _compat.HAS_JAX",
        "from repro.core import PAPER_DEFAULT, periodic_a2a",
        "from repro.core.batchsim import BatchLane, batch_run",
        "from repro.core.batchsim_jax import jax_available",
        "assert not jax_available()",
        "cm = PAPER_DEFAULT.replace(delta=1e-3)",
        "lanes = [BatchLane(schedule=periodic_a2a(8, 1), m_bytes=1e6)]",
        "res = batch_run(lanes, cm, backend='auto')",
        "assert res.backend == 'numpy' and res.fast_path.all()",
        "try:",
        "    batch_run(lanes, cm, backend='jax')",
        "except ImportError as e:",
        "    assert 'jax' in str(e)",
        "else:",
        "    raise AssertionError('backend=jax should raise without jax')",
        "try:",
        "    _compat.require_jax('a collective')",
        "except ImportError as e:",
        "    assert 'a collective' in str(e)",
        "else:",
        "    raise AssertionError('require_jax should raise without jax')",
        "import repro.collectives",
        "try:",
        "    repro.collectives.bruck_all_to_all",
        "except ImportError:",
        "    pass",
        "else:",
        "    raise AssertionError('collectives should raise without jax')",
        "print('ok')",
    ])
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(tmp_path), src])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "ok" in out.stdout
