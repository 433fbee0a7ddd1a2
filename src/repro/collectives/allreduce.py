"""AllReduce implementations: BRIDGE (Bruck RS + AG), RING, and psum oracle.

All functions are designed to be called inside `jax.shard_map` with a named
axis.  `bridge_all_reduce` is the paper's technique end-to-end: Rabenseifner
decomposition with a BRIDGE-scheduled Reduce-Scatter (early reconfigurations)
followed by a BRIDGE-scheduled AllGather (late reconfigurations).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schedules import Schedule

from .bruck_rs_ag import bruck_all_gather, bruck_reduce_scatter


def _shift_perm(n: int, offset: int) -> list[tuple[int, int]]:
    return [(i, (i + offset) % n) for i in range(n)]


def _to_chunks(x: jax.Array, n: int) -> tuple[jax.Array, int]:
    """Flatten x and pad so it splits into n equal chunks: (n, chunk)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(n, -1), pad


def _from_chunks(chunks: jax.Array, pad: int, shape, dtype) -> jax.Array:
    flat = chunks.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)


# --- Ring (bandwidth-optimal baseline; paper Section 2) ----------------------


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """x: (n, ...) contributions; device i returns reduced block i.
    n - 1 unit-offset steps (neighbor-only: no congestion, minimal bytes)."""
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {n}")
    if n == 1:
        return x[0]
    i = jax.lax.axis_index(axis_name)
    acc = x
    for t in range(n - 1):
        send_idx = (i - 1 - t) % n
        val = jnp.take(acc, send_idx, axis=0)
        recv = jax.lax.ppermute(val, axis_name, _shift_perm(n, 1))
        acc = acc.at[(i - 2 - t) % n].add(recv)
    return jnp.take(acc, i, axis=0)


def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """x: (...) local block; returns (n, ...): n - 1 unit-offset steps."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x[None]
    i = jax.lax.axis_index(axis_name)
    buf = jnp.zeros((n,) + x.shape, x.dtype).at[i].set(x)
    for t in range(n - 1):
        send_idx = (i - t) % n
        val = jnp.take(buf, send_idx, axis=0)
        recv = jax.lax.ppermute(val, axis_name, _shift_perm(n, 1))
        buf = buf.at[(i - 1 - t) % n].set(recv)
    return buf


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Bandwidth-optimal ring allreduce (sum), any shape."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    chunks, pad = _to_chunks(x, n)
    mine = ring_reduce_scatter(chunks, axis_name)
    full = ring_all_gather(mine, axis_name)
    return _from_chunks(full, pad, x.shape, x.dtype)


# --- BRIDGE / Bruck -----------------------------------------------------------


def bruck_all_reduce(
    x: jax.Array,
    axis_name: str,
    rs_schedule: Schedule | None = None,
    ag_schedule: Schedule | None = None,
) -> jax.Array:
    """AllReduce (sum) via Bruck RS + Bruck AG in 2*log2(n) steps.

    With schedules given, the permute chain follows the BRIDGE subring
    store-and-forward execution (see bruck_rs_ag docstring)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    chunks, pad = _to_chunks(x, n)
    mine = bruck_reduce_scatter(chunks, axis_name, rs_schedule)
    full = bruck_all_gather(mine, axis_name, ag_schedule)
    return _from_chunks(full, pad, x.shape, x.dtype)


def bridge_all_reduce(
    x: jax.Array,
    axis_name: str,
    n: int,
    m_bytes: float | None = None,
    cost_model=None,
    paper_faithful: bool = True,
) -> jax.Array:
    """The paper's AllReduce: optimal-R BRIDGE schedules for both phases.

    n must be the static axis size (schedules are synthesized at trace time).
    """
    from repro.core import plan
    from repro.core.cost_model import TPU_V5E

    cm = cost_model or TPU_V5E
    if m_bytes is None:
        m_bytes = float(x.size * x.dtype.itemsize)
    rs = plan("rs", n, m_bytes, cm, paper_faithful=paper_faithful).schedule
    ag = plan("ag", n, m_bytes, cm, paper_faithful=paper_faithful).schedule
    return bruck_all_reduce(x, axis_name, rs, ag)
