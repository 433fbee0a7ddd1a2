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

How the blocks move.  The buffer is kept as n separate blocks (slots), never
as one (n, ...) array, so no step gathers or scatters a whole buffer:

1. Slot j starts as block (i + j) % n of `x` (i = own axis index), a
   dynamic slice of the input.  A slot is copied out of `x` only when a
   round first sends it; slot 0 is never sent and stays in `x`.
2. Round k sends every slot whose bit k is set as one `ppermute` of that
   tuple of blocks to the device at offset +2^k, and the received blocks
   take their slots' places.  On the wire: the same ceil(log2 n) rounds and
   bytes as the textbook Bruck exchange (n = 4: two rounds of half the
   buffer).  Locally: nothing is copied between rounds.
3. Slot j now holds the block that device (i - j) % n sent to this one, so
   out[p] = slot[(i - p) % n].  A `lax.switch` on i picks the static order
   for this device and stacks the slots into the output in one pass: the
   output is written once, and each slot (slot 0 straight from `x`) is read
   once.  A roll of the stacked slots by the traced i would lower to a
   concatenate of two buffers and a slice of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bruck import num_steps


def _shift_perm(n: int, offset: int) -> list[tuple[int, int]]:
    """ppermute permutation: device i sends to (i + offset) mod n."""
    return [(i, (i + offset) % n) for i in range(n)]


def _assemble(n: int, me: int):
    """Output of device `me` from `x` and received slots 1..n-1."""
    def assemble(x, *received):
        slots = (x[me],) + received
        return jnp.stack([slots[(me - p) % n] for p in range(n)])
    return assemble


def bruck_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """Log-step all-to-all; x.shape[0] must equal the axis size."""
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {n}")
    if n == 1:
        return x
    i = jax.lax.axis_index(axis_name)

    # Phase 1 — slot j holds the block destined for (i + j) % n (slot 0 is x[i]).
    slots = [None] + [jax.lax.dynamic_index_in_dim(x, (i + j) % n, 0, False)
                      for j in range(1, n)]

    # Phase 2 — round k sends the slots whose k-th bit is set to the device at
    # offset +2^k.  Slot sets are static (independent of i).
    for k in range(num_steps(n)):
        send = [j for j in range(1, n) if (j >> k) & 1]
        moved = jax.lax.ppermute(tuple(slots[j] for j in send), axis_name,
                                 _shift_perm(n, 2**k))
        for j, block in zip(send, moved):
            slots[j] = block

    # Phase 3 — slot j holds the block that originated at (i - j) % n, so
    # out[p] = slot[(i - p) % n]: one static order per device.
    return jax.lax.switch(i, [_assemble(n, me) for me in range(n)],
                          x, *slots[1:])
