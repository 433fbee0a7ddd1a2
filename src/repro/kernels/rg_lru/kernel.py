"""RG-LRU linear-recurrence Pallas kernel.

Grid (B, num_d_blocks, num_t_blocks); the time dimension is innermost and
sequential ("arbitrary"), the batch and feature dimensions are parallel.
The hidden state h (block_d,) lives in VMEM scratch and is carried across
time blocks — HBM traffic is exactly one read of (a, b) and one write of y
per element, the memory-bound optimum for a first-order recurrence.

Within a time block the scan is an explicit fori_loop of VPU elementwise
ops (the recurrence is data-dependent so the MXU is not involved); block_d
is a multiple of 128 for lane alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rg_lru_kernel(a_ref, b_ref, y_ref, hlast_ref, h_scr, *, block_t: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)  # (block_t, block_d)
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h = carry
        h = a[t] * h + b[t]
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ti == nt - 1)
    def finalize():
        hlast_ref[0, :] = h.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def rg_lru_fwd(a, b, *, block_t: int = 256, block_d: int = 256,
               interpret: bool = True):
    """a, b: (B, T, D) -> (y: (B, T, D), h_last: (B, D))."""
    B, T, D = a.shape
    block_t = min(block_t, T)
    block_d = min(block_d, D)
    pt, pd = (-T) % block_t, (-D) % block_d
    if pt or pd:
        # pad with a=1, b=0 (identity steps) so h_last stays correct
        a = jnp.pad(a, ((0, 0), (0, pt), (0, pd)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pt), (0, pd)))
    Tp, Dp = T + pt, D + pd

    grid = (B, Dp // block_d, Tp // block_t)
    y, h_last = pl.pallas_call(
        functools.partial(_rg_lru_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, block_t, block_d), lambda bi, di, ti: (bi, ti, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, block_d), lambda bi, di, ti: (bi, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Dp), a.dtype),
            jax.ShapeDtypeStruct((B, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rg_lru_scan",
    )(a, b)
    return y[:, :T, :D], h_last[:, :D]
