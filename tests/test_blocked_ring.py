"""The Section 3.7 blocked ring in event-scored planning.

On a port-limited OCS (``ports`` < 2n) blocks of B = ceil(2n / ports) nodes
share one optical port pair.  Checked on the CPU at small n, for every
kind, at port counts giving B = 1, 2, 4 and 3: per-step hops equal the
analytic floor `BlockedRing.effective_hops` wherever a circuit of whole
blocks realizes the link offset, and the static ring's elsewhere, with the
analytic floor itself as it was before the event model; the scalar oracle
(`FabricSim`), the NumPy batch engine and the JAX kernel agree bit for
bit; B = 1 is the full-port fabric bit for bit; the verifier catches a plan
scored on the wrong blocks; and the backend choice keyed to the device.
"""
import dataclasses
import itertools

import numpy as np
import pytest

from repro.analysis import verify_plan
from repro.analysis.verifier import verify_tape
from repro.core import PAPER_DEFAULT
from repro.core.batchsim import BatchLane, batch_run, compile_tape
from repro.core.bruck import step_counts
from repro.core.fabricsim import FabricSim
from repro.core.schedules import Schedule
from repro.core.simulator import collective_time
from repro.core.subrings import BlockedRing, block_size, blocked_successor
from repro.planner import FabricKind, Planner, PlanRequest

MB = 2.0 ** 20
CM = PAPER_DEFAULT.replace(delta=1e-4)
CHUNKS = 4

# (n, r, ports) -> B: 1 (ports = 2n), 2, 4, and 3 twice: with radix 3 a
# circuit of 9 shortens steps; with radix 2 no power of two is a multiple
# of 3, so the event engine keeps the static ring on every step, where the
# analytic floor reads (offset / g) * 3 for g > 3
FABRICS = [(16, 2, 32), (16, 2, 16), (32, 2, 16), (27, 3, 18), (24, 2, 16)]
IDS = ["B1", "B2", "B4", "B3-radix3", "B3-static"]


def _schedules(kind, n, r, limit=16):
    S = len(step_counts(kind, n, r))
    xs = [(0,) + t for t in itertools.product((0, 1), repeat=S - 1)]
    return [Schedule(kind=kind, n=n, x=x, r=r) for x in xs[:limit]]


@pytest.mark.parametrize("n,r,ports", FABRICS, ids=IDS)
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_step_hops_are_the_analytic_block_floor(kind, n, r, ports):
    ring = BlockedRing(n=n, ports=ports)
    B = ring.block_size
    for sched in _schedules(kind, n, r):
        tape = compile_tape(sched, B)
        for off, g, hops in zip(tape.offsets, tape.g_step, tape.hops):
            floor = ring.effective_hops(off, g)
            if B == 1 or g % B == 0:          # a circuit of whole blocks
                assert hops == floor
            else:                             # the static ring, at or above
                assert hops == off >= floor
        assert not verify_tape(tape)


def _parent_floor(off, g, B):
    """The analytic floor as the analytic fabrics always applied it."""
    if B == 1:
        return off // g
    return off if g == 1 else min(off, off // g * B)


@pytest.mark.parametrize("n,r,ports", FABRICS, ids=IDS)
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_analytic_floor_is_unchanged(kind, n, r, ports):
    """`ocs`/`ocs-overlap` plans keep the floor they had before the event
    model: at B = 3 and radix 2 too, where the engine plays more hops."""
    B = block_size(n, ports)
    m = 16 * MB
    for sched in _schedules(kind, n, r):
        tape = compile_tape(sched)
        want = [_parent_floor(off, g, B)
                for off, g in zip(tape.offsets, tape.g_step)]
        bd = collective_time(sched, m, CM, ports=ports)
        assert [sc.hops for sc in bd.steps] == want


@pytest.mark.parametrize("n,r,ports", FABRICS, ids=IDS)
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_oracle_numpy_and_jax_agree_bit_for_bit(kind, n, r, ports):
    pytest.importorskip("jax")
    scheds = _schedules(kind, n, r, limit=8)
    lanes = [BatchLane(schedule=s, m_bytes=(1 + i) * MB, ports=ports)
             for i, s in enumerate(scheds)]
    numpy_run = batch_run(lanes, CM, chunks_per_msg=CHUNKS, backend="numpy")
    jax_run = batch_run(lanes, CM, chunks_per_msg=CHUNKS, backend="jax")
    assert numpy_run.certified.all() and jax_run.backend == "jax"
    sim = FabricSim(chunks_per_msg=CHUNKS, ports=ports)
    for i, lane in enumerate(lanes):
        oracle = sim.run(lane.schedule, lane.m_bytes, CM)
        assert oracle.completion == numpy_run.completion[i] \
            == jax_run.completion[i]
        assert oracle.step_done == tuple(numpy_run.step_done[i]) \
            == tuple(jax_run.step_done[i])
        assert oracle.chunks_moved == numpy_run.chunks_moved[i] == \
            n * CHUNKS * sum(compile_tape(lane.schedule,
                                          block_size(n, ports)).hops)


@pytest.mark.parametrize("n,r,ports", FABRICS[1:4], ids=IDS[1:4])
def test_skewed_blocked_lanes_match_the_oracle(n, r, ports):
    """Stragglers and skew take the guarded NumPy path (or the oracle):
    the gather follows the blocks there too."""
    rng = np.random.default_rng(n + ports)
    for sched in _schedules("a2a", n, r, limit=6):
        speed = tuple(rng.uniform(0.5, 1.5, n))
        scale = tuple(rng.uniform(0.5, 2.0, n))
        lane = BatchLane(schedule=sched, m_bytes=3 * MB, ports=ports,
                         link_speed=speed, payload_scale=scale)
        got = batch_run([lane], CM, chunks_per_msg=CHUNKS).completion[0]
        want = FabricSim(chunks_per_msg=CHUNKS, ports=ports,
                         link_speed=list(speed),
                         payload_scale=list(scale)).run(sched, 3 * MB, CM)
        assert got == want.completion


@pytest.mark.parametrize("ports", [None, 32, 33, 1000])
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_blocks_of_one_are_the_full_port_fabric(kind, ports):
    n = 16
    scheds = _schedules(kind, n, 2)
    lanes = [BatchLane(schedule=s, m_bytes=2 * MB) for s in scheds]
    ported = [dataclasses.replace(lane, ports=ports) for lane in lanes]
    a = batch_run(lanes, CM, chunks_per_msg=CHUNKS)
    b = batch_run(ported, CM, chunks_per_msg=CHUNKS)
    assert np.array_equal(a.completion, b.completion)
    assert np.array_equal(a.step_done, b.step_done)
    for s in scheds[:4]:
        assert FabricSim(chunks_per_msg=CHUNKS).run(s, 2 * MB, CM) == \
            FabricSim(chunks_per_msg=CHUNKS, ports=ports).run(s, 2 * MB, CM)
        assert compile_tape(s, block_size(n, ports)) == compile_tape(s)


@pytest.mark.parametrize("kind", ["a2a", "rs", "ag", "ar"])
def test_planner_with_ports_of_one_block_plans_as_full_port(kind):
    full = Planner(sim_chunks=CHUNKS).plan(PlanRequest(
        kind=kind, n=16, m_bytes=8 * MB, cost_model=CM,
        fabric=FabricKind.OCS_SIM))
    ported = Planner(sim_chunks=CHUNKS).plan(PlanRequest(
        kind=kind, n=16, m_bytes=8 * MB, cost_model=CM,
        fabric=FabricKind.OCS_SIM, ports=32))
    assert ported.predicted_time == full.predicted_time
    assert ported.alternatives == full.alternatives
    assert ported.breakdown == full.breakdown


def test_blocked_fabric_charges_the_shared_ports():
    """Every-step a2a at n = 16 on blocks of 2: each circuit hop is two
    hops, one through the block's shared optical port, so the lane serves
    more chunks than at full port and finishes later."""
    sched = Schedule(kind="a2a", n=16, x=(0, 1, 1, 1))
    full = FabricSim(chunks_per_msg=CHUNKS).run(sched, 8 * MB, CM)
    blocked = FabricSim(chunks_per_msg=CHUNKS, ports=16).run(sched, 8 * MB, CM)
    # offsets 1, 2, 4, 8 on circuits 1, 2, 4, 8: full port 1 hop each; on
    # blocks of 2, g = 2 keeps the static ring (2 hops), 4 and 8 take 2
    assert compile_tape(sched, 2).hops == (1, 2, 2, 2)
    assert blocked.chunks_moved == 16 * CHUNKS * 7 > full.chunks_moved
    assert blocked.completion > full.completion
    # the circuit of g = 2 equals the static ring's: only two rewirings pay
    assert blocked.reconfigs_paid == 2 * 16 < full.reconfigs_paid


def test_blocked_route_passes_each_gateway_once_per_circuit_hop():
    n, B, g = 16, 4, 8
    stride = g - B + 1
    for u in range(n):
        path = [u]
        for _ in range(B):
            path.append(blocked_successor(path[-1], stride, B, n))
        assert path[-1] == (u + g) % n
        # exactly one optical hop, leaving the block's last node
        optical = [p for p in path[:-1] if p % B == B - 1]
        assert len(optical) == 1


def test_blocks_must_tile_the_ring():
    sched = Schedule(kind="a2a", n=16, x=(0, 0, 0, 0))
    with pytest.raises(ValueError, match="tile"):
        compile_tape(sched, 3)
    with pytest.raises(ValueError, match="tile"):
        BatchLane(schedule=sched, m_bytes=MB, ports=11)   # B = 3
    with pytest.raises(ValueError, match="tile"):
        PlanRequest(kind="a2a", n=16, m_bytes=MB,
                    fabric=FabricKind.OCS_SIM, ports=11)
    with pytest.raises(ValueError, match="full-port"):
        FabricSim(ports=16).run_trace([(sched, MB)], CM)


def _blocked_plan(kind, ports, n=32):
    return Planner(sim_chunks=CHUNKS).plan(PlanRequest(
        kind=kind, n=n, m_bytes=64 * MB, cost_model=CM,
        fabric=FabricKind.OCS_SIM, ports=ports))


@pytest.mark.parametrize("kind", ["a2a", "rs", "ag", "ar"])
def test_verifier_holds_blocked_plans_to_the_plan_rules(kind):
    assert verify_plan(_blocked_plan(kind, 32)) == []


@pytest.mark.parametrize("kind", ["a2a", "rs", "ag", "ar"])
@pytest.mark.parametrize("planted", [64, 16], ids=["full_port", "blocks_of_4"])
def test_verifier_catches_a_plan_scored_on_other_blocks(kind, planted):
    """A plan made on blocks of 1 (or 4) passed off as one for blocks of 2:
    its breakdown's hops, or its scores, break plan/block."""
    res = _blocked_plan(kind, planted)
    lie = dataclasses.replace(
        res, request=dataclasses.replace(res.request, ports=32))
    rules = {v.rule for v in verify_plan(lie)}
    assert "plan/block" in rules, rules


def test_mutated_blocked_tape_is_caught():
    tape = compile_tape(Schedule(kind="a2a", n=16, x=(0, 0, 1, 1)), 2)
    rules = {v.rule for v in verify_tape(
        dataclasses.replace(tape, stride=(1, 1, 1, 1)))}
    assert "tape/block" in rules
    rules = {v.rule for v in verify_tape(
        dataclasses.replace(tape, hops=(1, 2, 1, 1)))}
    assert "tape/hops" in rules


# --- the backend choice, keyed to the device ---------------------------------


def _candidate_hops(kind, n, ports=None, cm=CM):
    """[lanes, S] hops of a planner candidate set, as `batch_run` sees it."""
    req = PlanRequest(kind=kind, n=n, m_bytes=64 * MB, cost_model=cm,
                      fabric=FabricKind.OCS_SIM, ports=ports)
    xs = {}
    for cand in Planner()._candidates(req, kind):
        xs.setdefault(cand.schedule.x, cand.schedule)
    return np.stack([compile_tape(s, req.block).hops for s in xs.values()])


def _choice(hops, n, C=8):
    from repro.core import batchsim

    return batchsim._resolve_backend(
        "auto", certify=True, certified=np.ones(len(hops), dtype=bool),
        n=n, C=C, hops=hops)


@pytest.mark.parametrize("kind,n,ports", [
    ("a2a", 24, None), ("rs", 96, None), ("a2a", 96, None),
    ("ag", 256, 320), ("a2a", 512, None), ("rs", 1024, None),
    ("a2a", 1024, None)])
def test_kind_without_an_entry_keeps_the_work_floor(kind, n, ports):
    pytest.importorskip("jax")
    from repro.core import batchsim, batchsim_jax

    assert batchsim_jax.prefers_device(np.ones((1, 1)), n, 8) is None
    hops = _candidate_hops(kind, n, ports)
    work = 8.0 * n * hops.sum()
    want = "jax" if work >= batchsim._JAX_AUTO_MIN_WORK else "numpy"
    assert _choice(hops, n) == want


def test_v5e_entry_puts_blocked_n256_sets_on_the_chip(monkeypatch):
    pytest.importorskip("jax")
    from repro.core import batchsim_jax
    from repro.core.cost_model import ocs_preset

    monkeypatch.setattr(batchsim_jax, "_device_kind", lambda: "TPU v5 lite")
    mems = ocs_preset("3d_mems_calient")
    for kind in ("a2a", "rs", "ag"):
        hops = _candidate_hops(kind, 256, 320, mems)
        # ~1e6 chunk-services: ~70-110 ms on the host, a few ms on the chip
        assert _choice(hops, 256) == "jax"
        assert batchsim_jax.predicted_seconds(hops, 256, 8) < 0.01
    assert _choice(_candidate_hops("a2a", 8), 8) == "numpy"
    assert _choice(_candidate_hops("rs", 1024), 1024) == "jax"


def test_compile_stats_count_real_and_blocked_lanes():
    pytest.importorskip("jax")
    from repro.core import batchsim_jax

    n, ports = 16, 16
    lanes = [BatchLane(schedule=s, m_bytes=MB, ports=ports)
             for s in _schedules("a2a", n, 2)[:3]]       # padded to 4
    before = batchsim_jax.compile_stats()
    batch_run(lanes, CM, chunks_per_msg=CHUNKS, backend="jax")
    after = batchsim_jax.compile_stats()
    tapes = [compile_tape(lane.schedule, 2) for lane in lanes]
    hops = sum(sum(t.hops) for t in tapes)
    full = sum(off // g for t in tapes for off, g in zip(t.offsets, t.g_step))
    assert after["lanes"] - before["lanes"] == 3
    assert after["blocked_lanes"] - before["blocked_lanes"] == 3
    assert after["hops"] - before["hops"] == hops
    assert after["blocked_hops"] - before["blocked_hops"] == hops - full > 0
    # a full-port batch adds lanes but no blocked ones
    batch_run([dataclasses.replace(lane, ports=None) for lane in lanes], CM,
              chunks_per_msg=CHUNKS, backend="jax")
    last = batchsim_jax.compile_stats()
    assert last["lanes"] - after["lanes"] == 3
    assert last["blocked_lanes"] == after["blocked_lanes"]
    assert last["blocked_hops"] == after["blocked_hops"]
