"""Bruck all-to-all on a JAX device axis (log-step, subring-patterned).

To be called *inside* `jax.shard_map` with a named mesh axis.  The input is
the local shard `x` of shape (n, ...) where row j is the block destined for
the device at axis index j.  Returns an array of the same shape whose row p
is the block received from device p — identical semantics to
`jax.lax.all_to_all(x, axis, 0, 0)` but communicated in ceil(log2 n) steps of
`ppermute` at offsets 2^k (the paper's Bruck pattern, Section 3.1), instead
of a single monolithic all-to-all.

On an OCS fabric each step is a single hop after a BRIDGE reconfiguration;
on a static TPU ICI ring the offset-2^k permute is routed by hardware over
min(2^k, n - 2^k) hops — the same h_k the cost model scores (DESIGN.md S3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bruck import num_steps


def _shift_perm(n: int, offset: int) -> list[tuple[int, int]]:
    """ppermute permutation: device i sends to (i + offset) mod n."""
    return [(i, (i + offset) % n) for i in range(n)]


def bruck_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """Log-step all-to-all; x.shape[0] must equal the axis size."""
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {n}")
    if n == 1:
        return x
    i = jax.lax.axis_index(axis_name)
    s = num_steps(n)

    # Phase 1 — local rotation: slot j holds the block destined for (i + j) % n.
    idx = (i + jnp.arange(n)) % n
    buf = jnp.take(x, idx, axis=0)

    # Phase 2 — s rounds: in round k send every slot whose k-th bit is set to
    # the device at offset +2^k.  Slot sets are static (independent of i).
    for k in range(s):
        send = np.array([j for j in range(n) if (j >> k) & 1], dtype=np.int32)
        moved = jax.lax.ppermute(buf[send], axis_name, _shift_perm(n, 2**k))
        buf = buf.at[send].set(moved)

    # Phase 3 — inverse rotation: output slot p = block that originated at p.
    # After phase 2, slot j holds the block destined for me that originated at
    # (i - j) % n, so out[p] = buf[(i - p) % n].
    out_idx = (i - jnp.arange(n)) % n
    return jnp.take(buf, out_idx, axis=0)
