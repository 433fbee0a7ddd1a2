"""The planning path's ``repro.*`` trace spans (`repro.core.spans`).

A live ``jax.profiler`` profile of small plans on the CPU is read back from
its ``.xplane.pb``: every span of docs/tracing.md appears, nested as stated,
with the counts it carries; the playback span's chunk-services equal the
chunks the engine reports moving, summed over the hop buckets of the call;
and the padding arithmetic of a vmapped bucket is checked by hand.  Spans are inert, and import nothing, in a
process that has not imported jax.
"""
import glob
import os
import subprocess
import sys
import tempfile
import warnings

import numpy as np
import pytest

from repro.core import PAPER_DEFAULT, batchsim, periodic_a2a
from repro.core.batchsim import BatchLane, batch_run, compile_tape

jax = pytest.importorskip("jax")

from repro.core import batchsim_jax  # noqa: E402
from repro.core.batchsim_jax import chunk_services  # noqa: E402
from repro.planner import FabricKind, Planner, PlanRequest  # noqa: E402

MB = 2.0 ** 20
CM = PAPER_DEFAULT.replace(delta=1e-3)
CHUNKS = 4


def profile_spans(run):
    """``run()`` under the profiler; its ``repro.*`` spans with parents.

    Each span is a dict: name, start, end (ns), args and parent (the index
    of the innermost span of the same thread that holds it, or None).
    """
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        spans = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in data.planes:
                if not plane.name.startswith("/host:"):
                    continue
                for line in plane.lines:
                    events = sorted(
                        ((e.name, e.start_ns, e.duration_ns, dict(e.stats))
                         for e in line.events if e.name.startswith("repro.")),
                        key=lambda e: (e[1], -e[2]))
                    stack: list[int] = []
                    for name, start, dur, args in events:
                        while stack and spans[stack[-1]]["end"] <= start:
                            stack.pop()
                        spans.append({"name": name, "start": start,
                                      "end": start + dur, "args": args,
                                      "parent": stack[-1] if stack else None})
                        stack.append(len(spans) - 1)
    return spans


def parent_name(spans, s):
    return None if s["parent"] is None else spans[s["parent"]]["name"]


def request(kind, m_bytes=2 * MB):
    return PlanRequest(kind=kind, n=24, m_bytes=m_bytes, cost_model=CM,
                       fabric=FabricKind.OCS_SIM)


@pytest.fixture(scope="module")
def planned():
    """Spans of an a2a miss, the same a2a again (a hit), and an ar miss,
    with every `batch_run` result the plans made."""
    planner = Planner(sim_chunks=CHUNKS, sim_backend="jax")
    results = []
    real = batchsim.batch_run

    def recording(*args, **kwargs):
        results.append(real(*args, **kwargs))
        return results[-1]

    def run():
        planner.plan(request("a2a"))
        planner.plan(request("a2a"))
        planner.plan(request("ar"))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batchsim, "batch_run", recording)
        spans = profile_spans(run)
    return spans, results


def test_every_plan_has_a_span_with_its_request_number_kind_and_hit(planned):
    spans, _ = planned
    plans = [s for s in spans if s["name"] == "repro.plan"]
    assert [s["args"] for s in plans] == [
        {"req": 0, "kind": "a2a", "block": 1, "hit": 0},
        {"req": 1, "kind": "a2a", "block": 1, "hit": 1},
        {"req": 2, "kind": "ar", "block": 1, "hit": 0}]
    assert all(s["parent"] is None for s in plans)
    # a hit does no planning work
    hit = spans.index(plans[1])
    assert not [s for s in spans if s["parent"] == hit]


@pytest.mark.parametrize("name,parent", [
    ("repro.plan.candidates", "repro.plan"),
    ("repro.plan.score", "repro.plan"),
    ("repro.batch.tapes", "repro.plan.score"),
    ("repro.batch.certify", "repro.plan.score"),
    ("repro.playback", "repro.plan.score"),
    ("repro.plan.rank", "repro.plan"),
    ("repro.plan.verify", "repro.plan")])
def test_span_nests_under_its_stage(planned, name, parent):
    spans, _ = planned
    mine = [s for s in spans if s["name"] == name]
    assert mine
    assert {parent_name(spans, s) for s in mine} == {parent}


def test_spans_count_per_plan_stage(planned):
    spans, _ = planned
    a2a, ar = [i for i, s in enumerate(spans)
               if s["name"] == "repro.plan" and not s["args"]["hit"]]

    def under(i, name):
        return [s for s in spans if s["name"] == name
                and s["start"] >= spans[i]["start"]
                and s["end"] <= spans[i]["end"]]

    # an a2a plan scores one candidate set; an ar plan scores its RS and AG
    # sets and ranks each, then ranks the composite
    for i, sets in ((a2a, 1), (ar, 2)):
        for name in ("repro.plan.candidates", "repro.plan.score",
                     "repro.batch.tapes", "repro.batch.certify"):
            assert len(under(i, name)) == sets
        assert len(under(i, "repro.plan.rank")) == sets + (i == ar)
        assert len(under(i, "repro.plan.verify")) == 1
    for s in under(a2a, "repro.plan.candidates"):
        assert s["args"]["cands"] >= 1
    for s in spans:
        if s["name"] == "repro.plan.verify":
            assert s["args"] == {"violations": 0}


def test_playback_chunk_services_equal_the_chunks_moved(planned):
    spans, results = planned
    score = [s for s in spans if s["name"] == "repro.plan.score"]
    assert len(score) == len(results) == 3
    for s, res in zip(score, results):
        assert s["args"]["lanes"] == len(res)
        assert res.backend == "jax" and res.certified.any()
        plays = [p for p in spans if p["name"] == "repro.playback"
                 and spans[p["parent"]] is s]
        assert sum(p["args"]["lanes"] for p in plays) == res.certified.sum()
        assert sum(p["args"]["chunk_services"] for p in plays) == \
            res.chunks_moved[res.certified].sum()
        for p in plays:
            assert p["args"]["chunk_services_run"] >= \
                p["args"]["chunk_services"] > 0
    certify = [s for s in spans if s["name"] == "repro.batch.certify"]
    assert [s["args"]["certified"] for s in certify] == \
        [int(r.certified.sum()) for r in results]


def test_playback_spans_cover_every_bucket(monkeypatch):
    """One span per playback call, however many hop buckets it plays: it
    carries the buckets and their padded lanes, and its run count is the sum
    of every bucket's, padding lanes included."""
    # no per-call or per-trip cost: these small lanes split into several
    # padded buckets, as n = 1024 candidate sets do on the chip
    monkeypatch.setattr(batchsim_jax, "_CALL_S", 0.0)
    monkeypatch.setattr(batchsim_jax, "_TRIP_S", 0.0)
    n = 8
    lanes = [BatchLane(schedule=periodic_a2a(n, i % 3), m_bytes=(1 + i) * MB)
             for i in range(70)]
    out = []
    spans = profile_spans(lambda: out.append(
        batch_run(lanes, CM, chunks_per_msg=CHUNKS, backend="jax")))
    (res,) = out
    hops = np.stack([compile_tape(lane.schedule).hops for lane in lanes])
    buckets = batchsim_jax.partition(hops, n, CHUNKS)
    runs = [int(batchsim_jax.padded_lanes(b.size)) for b in buckets]
    assert len(buckets) >= 2 and sum(runs) > len(lanes)
    (play,) = [s for s in spans if s["name"] == "repro.playback"]
    assert play["args"]["lanes"] == 70
    assert play["args"]["buckets"] == len(buckets)
    assert play["args"]["lanes_run"] == sum(runs)
    assert play["args"]["chunk_services"] == res.chunks_moved.sum()
    assert play["args"]["chunk_services_run"] == sum(
        chunk_services(hops[b], n, CHUNKS, lr)[1]
        for b, lr in zip(buckets, runs))
    assert not [s for s in spans if s["name"] == "repro.batch.host_play"]


def test_host_playback_has_its_span():
    lanes = [BatchLane(schedule=periodic_a2a(8, 1), m_bytes=MB)]
    spans = profile_spans(lambda: batch_run(lanes, CM, backend="numpy"))
    (play,) = [s for s in spans if s["name"] == "repro.batch.host_play"]
    assert play["args"] == {"lanes": 1, "blocked_hops": 0}
    assert not [s for s in spans if s["name"] == "repro.playback"]


@pytest.mark.parametrize("hops,needed,run", [
    ([[1, 1], [3, 1]], 6, 8),            # pad share 1 - 6/8 = 0.25
    ([[2, 5, 1]], 8, 8),                 # one lane pads nothing
    ([[1, 2], [1, 2], [1, 2]], 9, 9),    # equal lanes pad nothing
    ([[4, 0], [0, 4]], 8, 16),           # maxima of different steps add up
])
def test_chunk_services_count_the_bucket_padding(hops, needed, run):
    n, C = 5, 3
    assert chunk_services(np.array(hops), n, C) == (n * C * needed,
                                                    n * C * run)


@pytest.mark.parametrize("lanes_run,run", [(2, 8), (4, 16), (8, 32)])
def test_chunk_services_count_the_padding_lanes(lanes_run, run):
    """A bucket of 2 lanes, 4 trips, padded to ``lanes_run`` lanes: every
    padding lane runs the 4 trips too."""
    n, C = 5, 3
    assert chunk_services(np.array([[1, 1], [3, 1]]), n, C, lanes_run) == (
        n * C * 6, n * C * run)


def test_spans_are_inert_and_import_nothing_without_jax():
    """Until jax is imported no profiler can run: a plan then opens inert
    spans and never imports jax for them."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = "\n".join([
        "import sys",
        "from repro.core import PAPER_DEFAULT",
        "from repro.core.spans import span",
        "from repro.planner import FabricKind, Planner, PlanRequest",
        "with span('x', a=1) as s:",
        "    s.set_metadata(b=2)",
        "req = PlanRequest(kind='rs', n=24, m_bytes=2.0**20,",
        "                  cost_model=PAPER_DEFAULT, fabric=FabricKind.OCS_SIM)",
        "Planner(sim_backend='numpy').plan(req)",
        "assert 'jax' not in sys.modules, 'a span imported jax'",
        "print('ok')",
    ])
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_blocked_plan_spans_carry_block_and_blocked_hops():
    """A plan on a port-limited fabric: ``repro.plan`` carries its block
    size and ``repro.playback`` the hops the block floor adds."""
    req = PlanRequest(kind="a2a", n=16, m_bytes=2 * MB, cost_model=CM,
                      fabric=FabricKind.OCS_SIM, ports=16)
    results = []
    real = batchsim.batch_run

    def recording(*args, **kwargs):
        results.append(real(*args, **kwargs))
        return results[-1]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batchsim, "batch_run", recording)
        spans = profile_spans(lambda: Planner(
            sim_chunks=CHUNKS, sim_backend="jax").plan(req))
    (plan,) = [s for s in spans if s["name"] == "repro.plan"]
    assert plan["args"]["block"] == 2
    (play,) = [s for s in spans if s["name"] == "repro.playback"]
    (res,) = results
    tapes = [compile_tape(lane.schedule, 2) for lane in res.lanes]
    added = sum(h - off // g for t in tapes
                for h, off, g in zip(t.hops, t.offsets, t.g_step))
    assert play["args"]["blocked_hops"] == added > 0
