"""RWKV-6 recurrence Pallas kernel — chunked (intra-block parallel) form.

Grid (B * H, num_t_blocks), time sequential; the head state S (dk, dv) lives
in VMEM scratch across time blocks.  Within a block of L = block_t steps the
recurrence is evaluated *without* a sequential scan via the chunked
decomposition (GLA/Mamba-2-style, adapted to RWKV-6's per-channel decay):

  c_t   = sum_{tau<=t} log w_tau                      (cumulative log-decay)
  A[t,j] = sum_d r_t[d] k_j[d] e^{c_{t-1}[d]-c_j[d]}  (j <  t, intra-block)
  A[t,t] = sum_d r_t[d] u[d] k_t[d]                   (bonus diagonal)
  y_t   = (A @ V)[t] + (r_t * e^{c_{t-1}})^T S_in     (cross-block via state)
  S_out = e^{c_{L-1}} * S_in + sum_j (k_j e^{c_{L-1}-c_j}) v_j^T

All exponents are differences of cumulative sums with the *later* index on
the left, hence <= 0: every e^{...} is in (0, 1] — numerically safe in f32
(no 1/w blowups).  The (L, L, dk) pairwise tensor stays in VMEM:
L=64, dk=64 -> 1 MB.  MXU does the A@V and r@S matmuls.

HBM traffic: one read of r/k/v/w, one write of y per element, plus the
carried state — the memory-bound optimum for this op.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, slast_ref, s_scr,
                 *, block_t: int):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)    # (L, dk)
    k = k_ref[0].astype(jnp.float32)    # (L, dk)
    v = v_ref[0].astype(jnp.float32)    # (L, dv)
    lw = lw_ref[0].astype(jnp.float32)  # (L, dk) log-decay (<= 0)
    u = u_ref[0].astype(jnp.float32)    # (dk,)
    S = s_scr[...]                      # (dk, dv)
    L = block_t

    c = jnp.cumsum(lw, axis=0)          # c[t] = sum_{tau<=t} lw
    c_prev = c - lw                     # c[t-1] with c[-1] = 0

    # pairwise decay factors e^{c_prev[t] - c[j]} for j < t (exponent <= 0)
    expo = c_prev[:, None, :] - c[None, :, :]          # (L, L, dk)
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)  # strict lower
    decay = jnp.exp(jnp.where(tri[:, :, None], expo, 0.0))
    A = jnp.einsum("td,jd,tjd->tj", r, k, decay,
                   preferred_element_type=jnp.float32)
    A = jnp.where(tri, A, 0.0)
    A += jnp.diag(jnp.sum(r * u[None, :] * k, axis=1))  # bonus diagonal

    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y += jax.lax.dot_general(r * jnp.exp(c_prev), S, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, :, :] = y.astype(y_ref.dtype)

    c_last = c[L - 1]                                   # (dk,)
    k_scaled = k * jnp.exp(c_last[None, :] - c)         # e^{c_last - c_j} <= 1
    S_new = jnp.exp(c_last)[:, None] * S + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    @pl.when(ti == nt - 1)
    def finalize():
        slast_ref[0] = S_new.astype(slast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6_fwd(r, k, v, log_w, u, *, block_t: int = 64, interpret: bool = True):
    """r,k,log_w: (B,H,T,dk); v: (B,H,T,dv); u: (H,dk) -> (y, s_last)."""
    B, H, T, dk = r.shape
    dv = v.shape[-1]
    block_t = min(block_t, T)
    pt = (-T) % block_t
    if pt:
        # identity padding: log_w = 0 (decay 1), k = 0 (no state update)
        pad4 = ((0, 0), (0, 0), (0, pt), (0, 0))
        r = jnp.pad(r, pad4)
        k = jnp.pad(k, pad4)
        v = jnp.pad(v, pad4)
        log_w = jnp.pad(log_w, pad4)
    Tp = T + pt

    def fold(x):
        return x.reshape(B * H, Tp, x.shape[-1])
    rf, kf, vf, lwf = fold(r), fold(k), fold(v), fold(log_w)
    uf = jnp.broadcast_to(u[None], (B, H, dk)).reshape(B * H, dk)

    grid = (B * H, Tp // block_t)
    y, s_last = pl.pallas_call(
        functools.partial(_wkv6_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, dk), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, block_t, dk), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, block_t, dv), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, block_t, dk), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, dk), lambda bh, ti: (bh, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, dv), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ti: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, dv), r.dtype),
            jax.ShapeDtypeStruct((B * H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="wkv6_chunked",
    )(rf, kf, vf, lwf, uf)
    return (y.reshape(B, H, Tp, dv)[:, :, :T],
            s_last.reshape(B, H, dk, dv))
