"""Bruck-pattern reduce-scatter and all-gather on a JAX device axis.

Both are written in *relative block coordinates* (block r at device i refers
to global block (i + r) mod n for RS, (i - r) mod n for AG) so every device
executes the same static slot schedule — the cyclic symmetry that makes
Bruck's pattern subring-friendly (paper Section 3.1).

Data volumes per step match the paper exactly for power-of-two n:
  RS step k sends n / 2^{k+1} blocks  (m/2, m/4, ... — Section 3.4)
  AG step k sends 2^k blocks          (m/n, 2m/n, ... — Section 3.5)
Arbitrary axis sizes are handled by the remainder rule: a slot only
participates in a step when its target coordinate exists (< n), which is the
slot-level view of the mixed-radix digit classes in `repro.core.bruck`
(empty digit classes are simply skipped).

If a BRIDGE `Schedule` is supplied, each step is lowered as
h_k = offset_k / g ppermutes at the segment's subring link offset g —
store-and-forward along the reusable subring links, exactly the execution the
paper's cost model scores.  Without a schedule, each step is one ppermute at
the step offset (hardware-routed; the TPU default).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bruck import num_steps
from repro.core.schedules import Schedule


def _shift_perm(n: int, offset: int) -> list[tuple[int, int]]:
    return [(i, (i + offset) % n) for i in range(n)]


def _permute_hops(val: jax.Array, axis_name: str, n: int, offset: int,
                  link_offset: int) -> jax.Array:
    """Move val by +offset: either one hardware-routed permute or
    offset/link_offset store-and-forward hops along the subring links."""
    if link_offset == offset:
        return jax.lax.ppermute(val, axis_name, _shift_perm(n, offset))
    assert offset % link_offset == 0, (offset, link_offset)
    hops = offset // link_offset
    for _ in range(hops):
        val = jax.lax.ppermute(val, axis_name, _shift_perm(n, link_offset))
    return val


def _link_offsets(schedule: Schedule | None, s: int, offsets: list[int]) -> list[int]:
    if schedule is None:
        return list(offsets)  # one hardware-routed permute per step
    lo = schedule.link_offsets()
    assert len(lo) == s
    return lo


def bruck_reduce_scatter(x: jax.Array, axis_name: str,
                         schedule: Schedule | None = None) -> jax.Array:
    """x: (n, ...) local contributions; returns sum over devices of block i
    at device i (shape x.shape[1:]).  Equivalent to
    psum(x)[axis_index] but in log2(n) Bruck steps."""
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {n}")
    if n == 1:
        return x[0]
    i = jax.lax.axis_index(axis_name)
    s = num_steps(n)
    link = _link_offsets(schedule, s, [2**k for k in range(s)])

    # relative coords: buf[r] = my partial for global block (i + r) mod n
    buf = jnp.take(x, (i + jnp.arange(n)) % n, axis=0)
    for k in range(s):
        off = 2**k
        # active rows with bit k set: r = 2^k (mod 2^{k+1}); receiver merges
        # them at r - 2^k (rows = 0 mod 2^{k+1}).  Restricting to r < n is
        # the arbitrary-n remainder rule (digit classes empty above n).
        send = np.array([r for r in range(n) if r % (2 * off) == off], dtype=np.int32)
        moved = _permute_hops(buf[send], axis_name, n, off, link[k])
        buf = buf.at[send - off].add(moved)
    return buf[0]


def bruck_all_gather(x: jax.Array, axis_name: str,
                     schedule: Schedule | None = None) -> jax.Array:
    """x: (...) local block; returns (n, ...) with row p = device p's block.
    Equivalent to lax.all_gather(x, axis_name) in log2(n) Bruck steps with
    *decreasing* offsets 2^{s-1-k} (paper Section 3.5)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x[None]
    i = jax.lax.axis_index(axis_name)
    s = num_steps(n)
    offsets = [2 ** (s - 1 - k) for k in range(s)]
    link = _link_offsets(schedule, s, offsets)

    # relative coords: buf[r] = block of device (i - r) mod n
    buf = jnp.zeros((n,) + x.shape, x.dtype).at[0].set(x)
    held = [0]
    for k in range(s):
        off = offsets[k]
        # arbitrary-n remainder rule: only slots whose target coordinate
        # exists participate (time-reverse of the RS digit classes).
        send = np.array([r for r in sorted(held) if r + off < n], dtype=np.int32)
        moved = _permute_hops(buf[send], axis_name, n, off, link[k])
        buf = buf.at[send + off].set(moved)
        held = held + [r + off for r in held if r + off < n]
    assert sorted(held) == list(range(n))
    # out[p] = block from device p = buf[(i - p) mod n]
    return jnp.take(buf, (i - jnp.arange(n)) % n, axis=0)
