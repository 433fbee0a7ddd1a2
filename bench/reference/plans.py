"""The comparison that decides ``correct`` for event-scored plans.

Each checked answer is one plan the window produced: the request sent
(kind, m_bytes) and the program's result. Two numbers come out:

``score_rel_gap``    the worst |program score - reference completion| /
                     reference over every alternative the checked plans
                     rank (device playback, and the composite all-reduce's
                     sum of its phases);
``plan_violations``  how many rules of a plan the checked answers break:
                     the request it answers, valid schedules of the right
                     kind, one alternative per schedule, every R from 0 to
                     S-1 offered, alternatives in order of score with the
                     winner first, predicted time = score, and the winner
                     the best alternative by the reference's completions
                     too, to within ``rank_tol`` of the best (ranking).

The reference reads the result's fields and nothing else of the program.
"""
from __future__ import annotations

import numpy as np

from bench.reference import fabric


def _violations(kind: str, n: int, m_bytes: float, res) -> list[str]:
    bad = []
    req = res.request
    if (req.kind, req.n, float(req.m_bytes)) != (kind, n, float(m_bytes)):
        bad.append(f"answers {req.kind} n={req.n} m={req.m_bytes}, "
                   f"not {kind} n={n} m={m_bytes}")
        return bad
    alts = res.alternatives
    if not alts:
        return ["no alternatives"]
    scores = [a.score for a in alts]
    if scores != sorted(scores):
        bad.append("alternatives are not in order of score")
    if res.strategy != alts[0].strategy or res.predicted_time != alts[0].score:
        bad.append("the winner is not the first alternative")
    if any(a.predicted_time != a.score for a in alts):
        bad.append("an alternative's predicted time is not its score")
    S = n.bit_length() - 1
    if kind == "ar":
        for sched, k in ((res.rs_schedule, "rs"), (res.ag_schedule, "ag")):
            if sched is None or sched.kind != k or len(sched.x) != S:
                bad.append(f"the {k} phase schedule is missing or malformed")
        return bad
    xs = [a.x for a in alts]
    for a in alts:
        try:
            fabric.link_offsets(kind, n, a.x)
        except (TypeError, ValueError):
            bad.append(f"{a.strategy}: invalid schedule {a.x}")
            continue
        if a.R != sum(a.x):
            bad.append(f"{a.strategy}: R={a.R} but x={a.x}")
    if len(set(xs)) != len(xs):
        bad.append("a schedule is offered twice")
    if {sum(x) for x in xs if x is not None} != set(range(S)):
        bad.append("not every R from 0 to S-1 is offered")
    if res.schedule is None or res.schedule.x != alts[0].x:
        bad.append("the winner's schedule is not the first alternative's")
    return bad


def reference_scores(kind: str, n: int, m_bytes: float, res, fab: dict,
                     chunks: int, dtype=np.float64) -> list[float]:
    """Reference completion of every alternative the plan ranks."""
    if kind == "ar":
        return [fabric.allreduce_completion(
            n, res.rs_schedule.x, res.ag_schedule.x, m_bytes, fab, chunks,
            dtype)]
    return [fabric.completion(kind, n, a.x, m_bytes, fab, chunks, dtype)
            for a in res.alternatives]


def compare(answers, n: int, fab: dict, chunks: int, rank_tol: float,
            control_dtype=None) -> tuple[dict, list[str]]:
    """The numbers over ``answers`` [(kind, m_bytes, result)], and what
    broke. With ``control_dtype`` the program's scores are replaced by the
    reference computed in that precision (the control)."""
    score_gap = 0.0
    broken: list[str] = []
    for kind, m, res in answers:
        bad = _violations(kind, n, m, res)
        broken += [f"{kind} m={m!r}: {b}" for b in bad]
        if bad:
            continue
        want = reference_scores(kind, n, m, res, fab, chunks)
        if control_dtype is None:
            got = [a.score for a in res.alternatives][: len(want)]
        else:
            got = reference_scores(kind, n, m, res, fab, chunks, control_dtype)
        for g, w in zip(got, want):
            score_gap = max(score_gap, abs(g - w) / w)
        pick = 0 if control_dtype is None else int(np.argmin(got))
        behind = (want[pick] - min(want)) / min(want)
        if behind > rank_tol:
            broken.append(f"{kind} m={m!r}: the winner is {behind!r} behind "
                          f"the best alternative by the reference")
    return ({"score_rel_gap": score_gap,
             "plan_violations": float(len(broken))}, broken)
