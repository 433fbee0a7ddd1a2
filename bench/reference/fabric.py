"""Plain reference of an event-scored plan: Bruck steps on a sparse OCS fabric.

The model (the paper's Section 2 parameters, played per chunk):

- Collective step k of a radix-2 Bruck collective on n nodes (n a power of
  two, s = log2 n steps) sends from every node u to u + offset_k a payload of
  ``m * blocks_k / n`` bytes:

      a2a  offset 2^k,        blocks n/2
      rs   offset 2^k,        blocks n/2^(k+1)
      ag   offset 2^(s-1-k),  blocks 2^k          (rs reversed)

- A schedule x (x[0] = 0) splits the steps into segments at every k with
  x[k] = 1. The fabric's circuits during a segment connect u to u + g, g the
  gcd of the segment's offsets, so step k takes offset_k / g hops, relayed
  store-and-forward through u + g, u + 2g, ...
- Every node's egress port is one FIFO server. A message is cut into C
  chunks; a chunk arriving at time a is served from max(free, a) for
  (bytes / C) / bandwidth seconds, and reaches the next node alpha_h later.
- A node injects step k's message alpha_s after it received the last chunk
  of its step k - 1 message (at time alpha_s for k = 0).
- A segment boundary that changes g stalls every port by delta after what
  it has served so far.
- The completion time is the last node's receive of its last step.

Ports are simulated as vectors over the n nodes, chunk by chunk. The FIFO
order is checked, not assumed: every port has to see its chunks arrive in
the order it serves them, or `completion` raises `OrderError`, since the
sequence below would then not be what a FIFO port does.

``dtype`` sets the precision of every time; float64 is the configuration's,
float32 is the control.
"""
from __future__ import annotations

import math

import numpy as np


class OrderError(RuntimeError):
    """A port's chunks arrived out of the order it served them."""


def bruck_steps(kind: str, n: int) -> list[tuple[int, int]]:
    """(offset, blocks) of every step of a radix-2 Bruck collective."""
    s = n.bit_length() - 1
    if n < 2 or 1 << s != n:
        raise ValueError(f"the reference needs n a power of two >= 2, got {n}")
    if kind == "a2a":
        return [(1 << k, n // 2) for k in range(s)]
    if kind == "rs":
        return [(1 << k, n >> (k + 1)) for k in range(s)]
    if kind == "ag":
        return [(1 << (s - 1 - k), 1 << k) for k in range(s)]
    raise ValueError(f"unknown collective kind {kind!r}")


def link_offsets(kind: str, n: int, x) -> list[int]:
    """Circuit offset g in force at every step of schedule ``x``."""
    offsets = [off for off, _ in bruck_steps(kind, n)]
    if len(x) != len(offsets) or x[0] != 0 or any(v not in (0, 1) for v in x):
        raise ValueError(f"bad schedule {x} for {kind} n={n}")
    starts = [k for k in range(len(x)) if k == 0 or x[k] == 1] + [len(x)]
    g = [0] * len(x)
    for a, b in zip(starts, starts[1:]):
        seg = math.gcd(*offsets[a:b])
        g[a:b] = [seg] * (b - a)
    return g


def completion(kind: str, n: int, x, m_bytes: float, fabric: dict,
               chunks: int, dtype=np.float64) -> float:
    """Completion time, in seconds, of one Bruck collective under ``x``.

    ``fabric`` holds ``alpha_s``, ``alpha_h`` (s), ``bandwidth`` (bytes/s)
    and ``delta`` (s).
    """
    t = np.dtype(dtype).type
    alpha_s, alpha_h = t(fabric["alpha_s"]), t(fabric["alpha_h"])
    delta = t(fabric["delta"])
    beta = t(1.0) / t(fabric["bandwidth"])
    steps = bruck_steps(kind, n)
    g = link_offsets(kind, n, x)
    free = np.zeros(n, dtype=dtype)            # port busy until
    ready = np.zeros(n, dtype=dtype)           # last receive of the step before
    last_arrival = np.full(n, -np.inf, dtype=dtype)
    for k, (offset, blocks) in enumerate(steps):
        if k > 0 and x[k] and g[k] != g[k - 1]:
            free = free + delta
        inject = ready + alpha_s
        tau = (t(m_bytes) * t(blocks) / t(n) / t(chunks)) * beta
        arrivals = [inject] * chunks
        for _ in range(offset // g[k]):
            done = []
            for a in arrivals:
                if np.any(a < last_arrival):
                    raise OrderError(
                        f"{kind} n={n} x={tuple(x)} step {k}: a chunk reaches "
                        f"a port before one the port served earlier")
                last_arrival = a
                free = np.maximum(free, a) + tau
                done.append(free)
            # the chunk a port served arrives at the port g further on
            arrivals = [np.roll(d, g[k]) + alpha_h for d in done]
        ready = arrivals[-1]
    return float(ready.max())


def allreduce_completion(n: int, rs_x, ag_x, m_bytes: float, fabric: dict,
                         chunks: int, dtype=np.float64) -> float:
    """Reduce-scatter then all-gather, with one rewiring between the two
    when the last rs circuit differs from the first ag circuit."""
    rs = completion("rs", n, rs_x, m_bytes, fabric, chunks, dtype)
    ag = completion("ag", n, ag_x, m_bytes, fabric, chunks, dtype)
    swap = link_offsets("rs", n, rs_x)[-1] != link_offsets("ag", n, ag_x)[0]
    t = np.dtype(dtype).type
    return float(t(rs) + t(ag) + (t(fabric["delta"]) if swap else t(0.0)))
