"""Unified planner tests: JSON round trip, registry plug-ins, parity with the
legacy per-R `plan()`, constraints, fabric semantics, and the all-R DP
relaxation savings."""
import pytest

from repro.core import PAPER_DEFAULT, collective_time, num_steps
from repro.core import schedules as core_schedules
from repro.planner import (Candidate, PlanRequest, PlanResult, Planner,
                           available_strategies, register_strategy,
                           unregister_strategy)

MB = 2**20

# the n x r grid of tests/test_schedules.py::test_plan_valid_at_acceptance_grid
GRID_NS = [6, 12, 48, 96, 384]
GRID_RS = [2, 3, 4]


# --- JSON (de)serialization ---------------------------------------------------


@pytest.mark.parametrize("kind,n,r", [("a2a", 64, 2), ("rs", 96, 3),
                                      ("ag", 48, 2)])
def test_plan_result_json_round_trip(kind, n, r):
    req = PlanRequest(kind=kind, n=n, m_bytes=16 * MB,
                      cost_model=PAPER_DEFAULT, r=r)
    res = Planner().plan(req)
    back = PlanResult.from_json(res.to_json())
    # bit-identical schedules and exact floats (json repr round trip)
    assert back.schedule == res.schedule
    assert back.schedule.x == res.schedule.x
    assert back.predicted_time == res.predicted_time
    assert back.breakdown == res.breakdown
    assert back.alternatives == res.alternatives
    assert back.request == res.request
    assert back == res


def test_plan_result_json_round_trip_allreduce():
    req = PlanRequest(kind="ar", n=48, m_bytes=4 * MB,
                      cost_model=PAPER_DEFAULT, fabric="ocs",
                      strategies=tuple(available_strategies()))
    res = Planner().plan(req)
    back = PlanResult.from_json(res.to_json())
    assert back.rs_schedule == res.rs_schedule
    assert back.ag_schedule == res.ag_schedule
    assert back == res
    # ring participated as an implementation-level alternative
    assert {a.impl for a in res.alternatives} == {"bruck", "ring"}


# --- Registry plug-in ---------------------------------------------------------


def test_registered_strategy_participates_in_selection():
    from repro.core import Schedule

    # a schedule no built-in family produces at n=16 (lens (3, 1))
    novel = Schedule(kind="a2a", n=16, x=(0, 0, 0, 1))

    @register_strategy("dummy-test", kinds=("a2a",), paper_faithful=False)
    def dummy(req, kind):
        yield Candidate("dummy-test", novel)

    try:
        # explicit selection: the plug-in is the only (and winning) candidate
        res = Planner().plan(PlanRequest(kind="a2a", n=16, m_bytes=1.0,
                                         strategies=("dummy-test",)))
        assert res.strategy == "dummy-test"
        assert res.schedule == novel
        # default selection: the plug-in shows up in the alternatives table
        res = Planner().plan(PlanRequest(kind="a2a", n=16, m_bytes=1.0))
        assert any(a.strategy == "dummy-test" for a in res.alternatives)
    finally:
        unregister_strategy("dummy-test")
    with pytest.raises(KeyError):
        Planner().plan(PlanRequest(kind="a2a", n=16, m_bytes=1.0,
                                   strategies=("dummy-test",)))


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError):
        register_strategy("periodic")(lambda req, kind: [])


# --- Parity with the legacy per-R plan() --------------------------------------


@pytest.mark.parametrize("n", GRID_NS)
@pytest.mark.parametrize("r", GRID_RS)
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_parity_with_legacy_plan(kind, n, r):
    """The Planner never does worse than the pre-planner per-R reference on
    the full acceptance grid (tolerance covers grouped vs per-step float
    summation in the exact-dp family)."""
    m = float(MB)
    legacy = core_schedules._legacy_plan(kind, n, m, PAPER_DEFAULT, r=r)
    res = Planner().plan(PlanRequest(kind=kind, n=n, m_bytes=m,
                                     cost_model=PAPER_DEFAULT, r=r))
    assert res.predicted_time <= legacy.predicted_time * (1 + 1e-12)
    # the winner is a real schedule whose simulated time matches the claim
    t = collective_time(res.schedule, m, PAPER_DEFAULT).total
    assert t == pytest.approx(res.predicted_time, rel=1e-12)


@pytest.mark.parametrize("n", [8, 64, 256])
@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_paper_families_bit_identical_to_per_r(kind, n):
    """pow2 r=2: the all-R DP reproduces the per-R DP schedules bit-for-bit
    for the paper's families (Table 1 pinning transfers to the planner)."""
    old = core_schedules._legacy_candidate_schedules(
        kind, n, 4.0 * MB, PAPER_DEFAULT, paper_faithful=True)
    new = core_schedules.candidate_schedules(
        kind, n, 4.0 * MB, PAPER_DEFAULT, paper_faithful=True)
    assert [(nm, s.x) for nm, s in old] == [(nm, s.x) for nm, s in new]


def test_plan_shim_matches_planner():
    """core.schedules.plan is a thin shim: same winner as the Planner."""
    res = Planner().plan(PlanRequest(kind="rs", n=96, m_bytes=16.0 * MB,
                                     cost_model=PAPER_DEFAULT, r=3))
    p = core_schedules.plan("rs", 96, 16.0 * MB, PAPER_DEFAULT, r=3)
    assert p.schedule == res.schedule
    assert p.predicted_time == res.predicted_time
    assert p.strategy == res.strategy


# --- Constraints, fabric, objective -------------------------------------------


def test_max_r_constraint_caps_reconfigurations():
    cm = PAPER_DEFAULT.replace(delta=0.0)  # unconstrained optimum is R=S-1
    m = 64.0 * MB
    free = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=m,
                                      cost_model=cm))
    assert free.schedule.R == num_steps(64) - 1
    capped = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=m,
                                        cost_model=cm, max_R=2))
    assert capped.schedule.R <= 2
    assert all(a.R <= 2 for a in capped.alternatives if a.R is not None)


def test_delta_budget_constraint():
    cm = PAPER_DEFAULT  # delta = 10 us
    res = Planner().plan(PlanRequest(kind="rs", n=256, m_bytes=64.0 * MB,
                                     cost_model=cm,
                                     delta_budget=2.5 * cm.delta))
    assert res.schedule.R <= 2


def test_allreduce_cap_covers_both_phases():
    """For composite 'ar' the reconfiguration cap applies to RS + AG
    together, with the best split across the phases."""
    cm = PAPER_DEFAULT
    free = Planner().plan(PlanRequest(kind="ar", n=256, m_bytes=64.0 * MB,
                                      cost_model=cm))
    free_R = free.rs_schedule.R + free.ag_schedule.R
    assert free_R > 2  # the cap below actually binds
    for cap_kw in ({"max_R": 2}, {"delta_budget": 2.5 * cm.delta}):
        res = Planner().plan(PlanRequest(kind="ar", n=256, m_bytes=64.0 * MB,
                                         cost_model=cm, **cap_kw))
        assert res.rs_schedule.R + res.ag_schedule.R <= 2
        # + at most one topology-transition delta (not counted against cap)
        assert res.breakdown.reconfig <= 3 * cm.delta
    # capped at the unconstrained optimum's total, the split recovers it
    res = Planner().plan(PlanRequest(kind="ar", n=256, m_bytes=64.0 * MB,
                                     cost_model=cm, max_R=free_R))
    assert res.predicted_time <= free.predicted_time * (1 + 1e-12)


def test_static_fabric_only_r0():
    res = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=4.0 * MB,
                                     cost_model=PAPER_DEFAULT,
                                     fabric="static"))
    assert res.schedule.R == 0
    assert all(a.R == 0 for a in res.alternatives if a.R is not None)


def test_objective_selects_scoring():
    # transmission objective must pick a schedule whose transmission term is
    # minimal among candidates, even if its total time is not
    req_t = PlanRequest(kind="rs", n=128, m_bytes=64.0 * MB,
                        cost_model=PAPER_DEFAULT.replace(alpha_h=5e-5),
                        objective="transmission")
    res_t = Planner().plan(req_t)
    res_time = Planner().plan(PlanRequest(
        kind="rs", n=128, m_bytes=64.0 * MB,
        cost_model=PAPER_DEFAULT.replace(alpha_h=5e-5)))
    tx = res_t.breakdown.transmission + res_t.breakdown.reconfig
    tx_time = res_time.breakdown.transmission + res_time.breakdown.reconfig
    assert tx <= tx_time * (1 + 1e-12)


def test_request_validation():
    with pytest.raises(ValueError):
        PlanRequest(kind="bogus", n=8, m_bytes=1.0)
    with pytest.raises(ValueError):
        PlanRequest(kind="a2a", n=1, m_bytes=1.0)
    with pytest.raises(ValueError):
        PlanRequest(kind="a2a", n=8, m_bytes=-1.0)
    with pytest.raises(ValueError):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="wireless")
    with pytest.raises(ValueError):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, objective="vibes")
    with pytest.raises(ValueError):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, ports=0)
    with pytest.raises(ValueError):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, ports=-4)


def test_alternatives_table_has_no_duplicate_schedules():
    """Family endpoints overlap (static == periodic(R=0), every-step ==
    periodic(R=S-1)); each schedule is evaluated and listed once."""
    res = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=4.0 * MB,
                                     cost_model=PAPER_DEFAULT))
    xs = [a.x for a in res.alternatives if a.x is not None]
    assert len(xs) == len(set(xs))
    names = {a.strategy for a in res.alternatives}
    assert "static" not in names and "every-step" not in names  # deduped
    # explicitly selected, the endpoint family still plans on its own
    res = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=4.0 * MB,
                                     cost_model=PAPER_DEFAULT,
                                     strategies=("static",)))
    assert res.strategy == "static" and res.schedule.R == 0


# --- ocs-overlap fabric (sparse reconfiguration, hidden-delta credit) ---------


def test_overlap_request_validation():
    with pytest.raises(ValueError, match="overlap"):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="ocs-overlap",
                    overlap=1.5)
    with pytest.raises(ValueError, match="ocs-overlap"):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, overlap=0.5)  # fabric 'ocs'
    req = PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="ocs-overlap",
                      overlap=0.9)
    assert req.overlap == 0.9


def test_overlap_request_json_round_trip():
    req = PlanRequest(kind="rs", n=48, m_bytes=4.0 * MB,
                      cost_model=PAPER_DEFAULT.replace(delta=1e-3),
                      fabric="ocs-overlap", overlap=0.9)
    res = Planner().plan(req)
    back = PlanResult.from_json(res.to_json())
    assert back.request.fabric == "ocs-overlap"
    assert back.request.overlap == 0.9
    assert back == res


def test_overlap_family_yields_only_on_overlap_fabric():
    # on the plain ocs fabric the family is empty -> explicit selection fails
    with pytest.raises(ValueError, match="no strategy"):
        Planner().plan(PlanRequest(kind="a2a", n=16, m_bytes=1.0 * MB,
                                   strategies=("overlap",)))
    res = Planner().plan(PlanRequest(kind="a2a", n=16, m_bytes=1.0 * MB,
                                     fabric="ocs-overlap", overlap=0.5,
                                     strategies=("overlap",)))
    assert res.strategy.startswith("overlap[")


def test_overlap_credit_prefers_more_reconfigurations():
    """At ms-scale delta the full-pause model stays near-static, but with
    most of delta hidden, higher-R schedules win — and the hidden-delta
    breakdown is cheaper than the plain-ocs winner's."""
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    plain = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=16.0 * MB,
                                       cost_model=cm))
    hidden = Planner().plan(PlanRequest(kind="a2a", n=64, m_bytes=16.0 * MB,
                                        cost_model=cm, fabric="ocs-overlap",
                                        overlap=0.95))
    assert hidden.schedule.R > plain.schedule.R
    assert hidden.predicted_time < plain.predicted_time
    # reconfig term reflects the credit: R * delta * (1 - overlap)
    expect = hidden.schedule.R * cm.delta_sparse(64, 0.95)
    assert hidden.breakdown.reconfig == pytest.approx(expect)


def test_overlap_full_credit_reduces_to_zero_reconfig_cost():
    cm = PAPER_DEFAULT.replace(delta=15e-3)
    res = Planner().plan(PlanRequest(kind="rs", n=32, m_bytes=8.0 * MB,
                                     cost_model=cm, fabric="ocs-overlap",
                                     overlap=1.0))
    assert res.breakdown.reconfig == 0.0
    # with delta free, the planner reconfigures aggressively
    assert res.schedule.R > 0


def test_overlap_allreduce_charges_sparse_transition():
    cm = PAPER_DEFAULT.replace(delta=1e-4)
    res = Planner().plan(PlanRequest(kind="ar", n=32, m_bytes=8.0 * MB,
                                     cost_model=cm, fabric="ocs-overlap",
                                     overlap=0.75))
    from repro.core import allreduce_time_overlap

    ref = allreduce_time_overlap(res.rs_schedule, res.ag_schedule,
                                 8.0 * MB, cm, 0.75)
    assert res.predicted_time == ref.total
    # regression: 'ocs-overlap' must plan the RS/AG phases (not fall into the
    # static-fabric branch) and dominate the plain-ocs winner under the same
    # hidden-delta scoring
    assert res.rs_schedule.R + res.ag_schedule.R > 0
    plain = Planner().plan(PlanRequest(kind="ar", n=32, m_bytes=8.0 * MB,
                                       cost_model=cm))
    plain_rescored = allreduce_time_overlap(plain.rs_schedule,
                                            plain.ag_schedule,
                                            8.0 * MB, cm, 0.75)
    assert res.predicted_time <= plain_rescored.total * (1 + 1e-12)


# --- ocs-sim fabric (batched event-scored planning) ----------------------------


def test_ocs_sim_request_validation():
    with pytest.raises(ValueError, match="time"):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="ocs-sim",
                    objective="latency")
    # the event engine plays the Section 3.7 blocked ring: ports are
    # accepted where the blocks tile the ring, and engage the block model
    req = PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="ocs-sim", ports=8)
    assert req.block == 2
    res = Planner().plan(PlanRequest(kind="a2a", n=8, m_bytes=MB,
                                     fabric="ocs-sim", ports=8))
    full = Planner().plan(PlanRequest(kind="a2a", n=8, m_bytes=MB,
                                      fabric="ocs-sim"))
    assert [a.predicted_time for a in res.alternatives if a.R] != \
        [a.predicted_time for a in full.alternatives if a.R]
    with pytest.raises(ValueError, match="ports"):
        PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="ocs-sim", ports=3)
    req = PlanRequest(kind="a2a", n=8, m_bytes=1.0, fabric="ocs-sim",
                      overlap=0.75)
    assert req.overlap == 0.75


def test_ocs_sim_scores_every_candidate_with_the_simulator():
    """Every schedule alternative's score is its batched event completion,
    and the winner minimizes it."""
    from repro.core.batchsim import batch_completion_times

    cm = PAPER_DEFAULT.replace(delta=1e-3)
    planner = Planner(sim_chunks=8)
    res = planner.plan(PlanRequest(kind="a2a", n=48, m_bytes=4.0 * MB,
                                   cost_model=cm, fabric="ocs-sim"))
    scheds = [core_schedules.Schedule(kind="a2a", n=48, x=a.x)
              for a in res.alternatives]
    sim = batch_completion_times(scheds, 4.0 * MB, cm, chunks_per_msg=8)
    for a, t in zip(res.alternatives, sim, strict=True):
        assert a.score == pytest.approx(float(t), rel=1e-12)
        assert a.predicted_time == a.score
    assert res.predicted_time == res.alternatives[0].score
    assert min(a.score for a in res.alternatives) == res.predicted_time


@pytest.mark.parametrize("kind", ["a2a", "rs", "ag"])
def test_ocs_sim_never_worse_than_analytic_winner(kind):
    """Acceptance: the ocs-sim winner is never a schedule the batched
    simulator ranks worse than the analytic (ocs-overlap) winner of the
    same request."""
    from repro.core.batchsim import batch_completion_times

    cm = PAPER_DEFAULT.replace(delta=1e-3)
    planner = Planner(sim_chunks=8)
    for overlap in (0.0, 0.75):
        sim_res = planner.plan(PlanRequest(
            kind=kind, n=96, m_bytes=4.0 * MB, cost_model=cm,
            fabric="ocs-sim", overlap=overlap))
        analytic = planner.plan(PlanRequest(
            kind=kind, n=96, m_bytes=4.0 * MB, cost_model=cm,
            fabric="ocs-overlap", overlap=overlap))
        both = batch_completion_times(
            [sim_res.schedule, analytic.schedule], 4.0 * MB, cm,
            overlap=overlap, chunks_per_msg=planner.sim_chunks)
        assert both[0] <= both[1] * (1 + 1e-12)


def test_ocs_sim_allreduce_plans_phases_with_event_scores():
    cm = PAPER_DEFAULT.replace(delta=1e-3)
    planner = Planner(sim_chunks=4)
    res = planner.plan(PlanRequest(kind="ar", n=32, m_bytes=8.0 * MB,
                                   cost_model=cm, fabric="ocs-sim",
                                   overlap=0.75))
    assert res.rs_schedule is not None and res.ag_schedule is not None
    # predicted time = simulated RS + simulated AG + sparse transition
    from repro.core.batchsim import batch_completion_times

    phases = batch_completion_times([res.rs_schedule, res.ag_schedule],
                                    8.0 * MB, cm, overlap=0.75,
                                    chunks_per_msg=4)
    rs_final = res.rs_schedule.link_offsets()[-1]
    ag_first = res.ag_schedule.link_offsets()[0]
    transition = cm.delta_sparse(32 if rs_final != ag_first else 0, 0.75)
    assert res.predicted_time == pytest.approx(
        float(phases[0] + phases[1]) + transition, rel=1e-12)


def test_ocs_sim_round_trip():
    req = PlanRequest(kind="rs", n=48, m_bytes=4.0 * MB,
                      cost_model=PAPER_DEFAULT, fabric="ocs-sim")
    res = Planner().plan(req)
    back = PlanResult.from_json(res.to_json())
    assert back.request.fabric == "ocs-sim"
    assert back == res


# --- plan cache + plan_batch (the serving path) --------------------------------


def test_plan_cache_hits_on_repeated_requests():
    planner = Planner(cache_size=8)
    req = PlanRequest(kind="a2a", n=48, m_bytes=4.0 * MB,
                      cost_model=PAPER_DEFAULT)
    r1 = planner.plan(req)
    r2 = planner.plan(PlanRequest(kind="a2a", n=48, m_bytes=4.0 * MB,
                                  cost_model=PAPER_DEFAULT))
    assert r1 is r2  # equal requests share one immutable result
    info = planner.cache_info()
    assert (info.hits, info.misses, info.size) == (1, 1, 1)
    # a different request misses
    planner.plan(PlanRequest(kind="rs", n=48, m_bytes=4.0 * MB,
                             cost_model=PAPER_DEFAULT))
    assert planner.cache_info().misses == 2
    planner.cache_clear()
    assert planner.cache_info() == (0, 0, 0, 8)


def test_plan_cache_lru_eviction():
    planner = Planner(cache_size=1)
    req_a = PlanRequest(kind="a2a", n=16, m_bytes=1.0 * MB)
    req_b = PlanRequest(kind="rs", n=16, m_bytes=1.0 * MB)
    ra = planner.plan(req_a)
    planner.plan(req_b)           # evicts req_a
    assert planner.cache_info().size == 1
    assert planner.plan(req_b) is not None
    assert planner.cache_info().hits == 1
    ra2 = planner.plan(req_a)     # re-planned, not cached
    assert planner.cache_info().misses == 3
    assert ra2 == ra              # deterministic: equal even when recomputed


def test_plan_cache_disabled():
    planner = Planner(cache_size=0)
    req = PlanRequest(kind="a2a", n=16, m_bytes=1.0 * MB)
    r1, r2 = planner.plan(req), planner.plan(req)
    assert r1 == r2 and r1 is not r2
    assert planner.cache_info() == (0, 0, 0, 0)
    with pytest.raises(ValueError, match="cache_size"):
        Planner(cache_size=-1)


def test_plan_batch_dedupes_repeated_traffic():
    planner = Planner(cache_size=16)
    reqs = [PlanRequest(kind="a2a", n=32, m_bytes=2.0 * MB),
            PlanRequest(kind="rs", n=32, m_bytes=2.0 * MB),
            PlanRequest(kind="a2a", n=32, m_bytes=2.0 * MB),
            PlanRequest(kind="a2a", n=32, m_bytes=2.0 * MB)]
    results = planner.plan_batch(reqs)
    assert len(results) == 4
    assert results[0] is results[2] is results[3]
    assert results[0].schedule.kind == "a2a"
    assert results[1].schedule.kind == "rs"
    info = planner.cache_info()
    assert (info.hits, info.misses) == (2, 2)


def test_default_planner_is_shared_and_cached():
    from repro.planner import default_planner

    planner = default_planner()
    assert planner is default_planner()
    before = planner.cache_info().hits
    req = PlanRequest(kind="ag", n=24, m_bytes=1.0 * MB)
    planner.plan(req)
    planner.plan(req)
    assert planner.cache_info().hits >= before + 1
    # the legacy shim routes through the same cache
    core_schedules.plan("ag", 24, 1.0 * MB, PAPER_DEFAULT)
    core_schedules.plan("ag", 24, 1.0 * MB, PAPER_DEFAULT)
    assert planner.cache_info().hits >= before + 2


# --- All-R DP performance ------------------------------------------------------


def test_all_r_dp_relaxation_savings():
    """Acceptance: planning the full candidate set at n=384 performs >= 5x
    fewer DP cell relaxations than the legacy per-R loop."""
    m = float(MB)
    core_schedules.clear_schedule_caches()
    core_schedules.reset_dp_stats()
    for kind in ("a2a", "rs", "ag"):
        core_schedules.candidate_schedules(kind, 384, m, PAPER_DEFAULT, r=2)
    relax_all = core_schedules.dp_stats()["relaxations"]
    core_schedules.reset_dp_stats()
    for kind in ("a2a", "rs", "ag"):
        core_schedules._legacy_candidate_schedules(kind, 384, m, PAPER_DEFAULT,
                                                   r=2)
    relax_per_r = core_schedules.dp_stats()["relaxations"]
    assert relax_per_r >= 5 * relax_all, (relax_per_r, relax_all)


def test_all_r_dp_matches_capped_dp_per_r():
    """best[i][r] is cap-independent: every all-R entry equals the capped
    per-R DP bit-for-bit (integer hop objective)."""
    steps = core_schedules._steps_cached("a2a", 96, 3)
    tables = core_schedules.SegmentTables(steps)
    s = len(steps)
    all_r = core_schedules._partition_dp_all(s, tables.hop_sum)
    for R in range(s):
        cost, lens = core_schedules._partition_dp(s, R + 1, tables.hop_sum)
        assert (cost, tuple(lens)) == all_r[R]


def test_segment_tables_match_naive_costs():
    """O(1) prefix/gcd segment costs equal the O(len) closures exactly for
    integer hop sums, and to float tolerance for transmission."""
    for (n, r) in ((96, 3), (384, 2), (48, 4)):
        steps = core_schedules._steps_cached("rs", n, r)
        tables = core_schedules.SegmentTables(steps)
        hop_naive = core_schedules._hop_sum_cost(steps)
        tx_naive = core_schedules._transmission_cost(steps)
        S = len(steps)
        for a in range(S):
            for b in range(a, S):
                assert tables.gcd(a, b) == core_schedules._segment_gcd(steps, a, b)
                assert tables.hop_sum(a, b) == hop_naive(a, b)
                assert tables.tx_sum(a, b) == pytest.approx(tx_naive(a, b),
                                                            rel=1e-12)


# --- plan_gradient_sync wrapper ------------------------------------------------


def test_plan_gradient_sync_is_thin_wrapper():
    """Unchanged public behavior: same winners/alternatives as planning an
    'ar' request directly."""
    from repro.collectives import plan_gradient_sync
    from repro.planner import default_strategy_names

    cm = PAPER_DEFAULT
    for fabric in ("static", "ocs"):
        p = plan_gradient_sync(64, 4.0 * MB, cm, fabric=fabric)
        res = Planner().plan(PlanRequest(
            kind="ar", n=64, m_bytes=4.0 * MB, cost_model=cm, fabric=fabric,
            strategies=default_strategy_names() + ("ring",)))
        assert p.impl == res.impl
        assert p.predicted_time == res.predicted_time
        if p.impl == "bruck" and fabric == "ocs":
            assert p.rs_schedule == res.rs_schedule
            assert p.ag_schedule == res.ag_schedule
        else:
            assert p.rs_schedule is None and p.ag_schedule is None
    # psum fallback unchanged
    p = plan_gradient_sync(1, 4.0 * MB, cm)
    assert (p.impl, p.predicted_time, p.alternatives) == ("psum", 0.0, {})
    p = plan_gradient_sync(64, 4.0 * MB, cm, allow=())
    assert p.impl == "psum"
