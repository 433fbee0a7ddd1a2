"""Plain reference of an event-scored plan on a port-limited OCS (the paper's
Section 3.7 blocked ring): Bruck steps played per chunk through per-port
FIFOs, in NumPy.

The fabric of `bench.reference.fabric`, with z optical ports for n nodes:

- Blocks of B = ceil(2n / z) consecutive nodes share one optical egress and
  one optical ingress port; B divides n. The last node of a block (its
  gateway) owns the optical egress, the first node the optical ingress, and
  every other link is the electrical link u -> u + 1.
- A segment's circuit offset g is the gcd of its steps' offsets, as at full
  port. If g > B and B divides g, every gateway's circuit goes to the first
  node of the block g further on; otherwise no circuit of whole blocks
  helps, and every gateway's circuit goes to the next block (the static
  ring). A chunk always moves from the node that served it to that node's
  successor: over the circuit from a gateway, over the electrical link from
  any other node.
- A step of offset o takes (o / g) * B hops where a circuit helps (B - 1
  electrical hops and one optical hop per circuit hop), else o hops.
- Every node's egress port is one FIFO server, as at full port; a segment
  boundary stalls every port by delta only where the gateways' circuits
  change; an all-reduce's rs -> ag boundary likewise.

The FIFO order is checked, not assumed: a chunk reaching a port before one
that port served earlier raises `fabric.OrderError`. ``dtype`` sets the
precision of every time; float64 is the configuration's, float32 the
control.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import fabric as full_port
from bench.reference import plans


def block_size(n: int, ports: int) -> int:
    """Nodes per block; they must tile the ring."""
    b = max(1, math.ceil(2 * n / ports))
    if n % b:
        raise ValueError(f"blocks of {b} nodes do not tile n={n}")
    return b


def successors(n: int, g: int, b: int) -> np.ndarray:
    """succ[u]: the node a chunk served at u goes to next."""
    jump = g - b + 1 if (b == 1 or (g > b and g % b == 0)) else 1
    u = np.arange(n)
    return np.where(u % b == b - 1, u + jump, u + 1) % n


def hops(offset: int, g: int, b: int) -> int:
    """Hops of a step of ``offset`` under circuit offset ``g``."""
    return offset // g * b if (b == 1 or (g > b and g % b == 0)) else offset


def completion(kind: str, n: int, x, m_bytes: float, fab: dict,
               chunks: int, b: int, dtype=np.float64) -> float:
    """Completion time, in seconds, of one Bruck collective under ``x`` on
    blocks of ``b`` nodes."""
    t = np.dtype(dtype).type
    alpha_s, alpha_h = t(fab["alpha_s"]), t(fab["alpha_h"])
    delta = t(fab["delta"])
    beta = t(1.0) / t(fab["bandwidth"])
    steps = full_port.bruck_steps(kind, n)
    g = full_port.link_offsets(kind, n, x)
    free = np.zeros(n, dtype=dtype)
    ready = np.zeros(n, dtype=dtype)
    last_arrival = np.full(n, -np.inf, dtype=dtype)
    circuit = None
    for k, (offset, blocks) in enumerate(steps):
        succ = successors(n, g[k], b)
        if circuit is not None and x[k] and not np.array_equal(succ, circuit):
            free = free + delta
        circuit = succ
        tau = (t(m_bytes) * t(blocks) / t(n) / t(chunks)) * beta
        arrivals = [ready + alpha_s] * chunks
        for _ in range(hops(offset, g[k], b)):
            done = []
            for a in arrivals:
                if np.any(a < last_arrival):
                    raise full_port.OrderError(
                        f"{kind} n={n} x={tuple(x)} step {k}: a chunk reaches "
                        f"a port before one the port served earlier")
                last_arrival = a
                free = np.maximum(free, a) + tau
                done.append(free)
            moved = []
            for d in done:
                nxt = np.empty_like(d)
                nxt[succ] = d
                moved.append(nxt + alpha_h)
            arrivals = moved
        ready = arrivals[-1]
    return float(ready.max())


def allreduce_completion(n: int, rs_x, ag_x, m_bytes: float, fab: dict,
                         chunks: int, b: int, dtype=np.float64) -> float:
    """Reduce-scatter then all-gather, with one rewiring between the two
    where the gateways' circuits differ."""
    rs = completion("rs", n, rs_x, m_bytes, fab, chunks, b, dtype)
    ag = completion("ag", n, ag_x, m_bytes, fab, chunks, b, dtype)
    swap = not np.array_equal(
        successors(n, full_port.link_offsets("rs", n, rs_x)[-1], b),
        successors(n, full_port.link_offsets("ag", n, ag_x)[0], b))
    t = np.dtype(dtype).type
    return float(t(rs) + t(ag) + (t(fab["delta"]) if swap else t(0.0)))


def reference_scores(kind: str, n: int, m_bytes: float, res, fab: dict,
                     chunks: int, b: int, dtype=np.float64) -> list[float]:
    """Reference completion of every alternative the plan ranks."""
    if kind == "ar":
        return [allreduce_completion(n, res.rs_schedule.x, res.ag_schedule.x,
                                     m_bytes, fab, chunks, b, dtype)]
    return [completion(kind, n, a.x, m_bytes, fab, chunks, b, dtype)
            for a in res.alternatives]


def compare(answers, n: int, fab: dict, chunks: int, ports: int,
            rank_tol: float, control_dtype=None) -> tuple[dict, list[str]]:
    """`plans.compare`'s numbers over ``answers`` [(kind, m_bytes, result)]
    on the blocked ring of ``ports`` ports; a plan that does not answer a
    request on those ports is broken."""
    b = block_size(n, ports)
    score_gap = 0.0
    broken: list[str] = []
    for kind, m, res in answers:
        bad = plans._violations(kind, n, m, res)
        if not bad and getattr(res.request, "ports", None) != ports:
            bad = [f"answers ports={getattr(res.request, 'ports', None)}, "
                   f"not {ports}"]
        broken += [f"{kind} m={m!r}: {x}" for x in bad]
        if bad:
            continue
        want = reference_scores(kind, n, m, res, fab, chunks, b)
        if control_dtype is None:
            got = [a.score for a in res.alternatives][: len(want)]
        else:
            got = reference_scores(kind, n, m, res, fab, chunks, b,
                                   control_dtype)
        for g, w in zip(got, want):
            score_gap = max(score_gap, abs(g - w) / w)
        pick = 0 if control_dtype is None else int(np.argmin(got))
        behind = (want[pick] - min(want)) / min(want)
        if behind > rank_tol:
            broken.append(f"{kind} m={m!r}: the winner is {behind!r} behind "
                          f"the best alternative by the reference")
    return ({"score_rel_gap": score_gap,
             "plan_violations": float(len(broken))}, broken)
