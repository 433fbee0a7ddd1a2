"""Flash-attention backward Pallas kernels (two-pass, MHA layout).

Standard flash backward with the logsumexp trick (saved from the forward):

  P_ij = exp(q_i k_j scale - L_i)
  D_i  = sum_d do_id * o_id
  dV_j = sum_i P_ij do_i
  dS_ij = P_ij * (do_i . v_j - D_i) * scale
  dQ_i = sum_j dS_ij k_j          (pass 2: k innermost, dq in scratch)
  dK_j = sum_i dS_ij q_i          (pass 1: q innermost, dk/dv in scratch)

GQA is handled by the caller (ops.py) by expanding K/V to the query heads
and group-summing dK/dV — the kernels are pure MHA.  Masking is identical to
the forward kernel (causal / sliding-window / padding), with the same
tile-level skipping, so backward FLOPs match the mask sparsity too.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mask_and_run(causal, window, off, sk, block_q, block_k, qi, ki):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < sk
    if causal:
        mask &= k_pos <= q_pos + off
    if window is not None:
        mask &= k_pos > q_pos + off - window
    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1 + off
    if window is not None:
        run_w = ki * block_k + block_k - 1 > qi * block_q + off - window
        run = jnp.logical_and(run, run_w) if causal else run_w
    return mask, run


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, window, block_q, block_k, off, sk):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    mask, run = _mask_and_run(causal, window, off, sk, block_q, block_k,
                              qi, ki)

    def compute():
        q = q_ref[0].astype(jnp.float32)      # (bq, d)
        k = k_ref[0].astype(jnp.float32)      # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)    # (bq, d)
        lse = lse_ref[0]                      # (bq,)
        dvec = dvec_ref[0]                    # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None]) * scale
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    if isinstance(run, bool):
        compute()
    else:
        pl.when(run)(compute)

    @pl.when(qi == nq - 1)
    def finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                   dq_ref, dq_scr, *,
                   scale, causal, window, block_q, block_k, off, sk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    mask, run = _mask_and_run(causal, window, off, sk, block_q, block_k,
                              qi, ki)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        dvec = dvec_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    if isinstance(run, bool):
        compute()
    else:
        pl.when(run)(compute)

    @pl.when(ki == nk - 1)
    def finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "block_q", "block_k",
                     "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, scale, causal, window,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = True):
    """MHA backward.  q,k,v,o,do: (B, H, S*, D); lse: (B, H, Sq) f32.

    Returns (dq, dk, dv) with k/v already expanded to H heads (GQA summing
    happens in ops.py)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pq, pk = (-sq) % block_q, (-sk) % block_k
    def pad_q(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else x

    def pad_k(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else x
    qf = pad_q(q).reshape(b * h, sq + pq, d)
    of = pad_q(o).reshape(b * h, sq + pq, d)
    dof = pad_q(do).reshape(b * h, sq + pq, d)
    kf = pad_k(k).reshape(b * h, sk + pk, d)
    vf = pad_k(v).reshape(b * h, sk + pk, d)
    # padded queries: lse pad of +inf makes p = exp(-inf) = 0 (no gradient)
    lsef = (jnp.pad(lse, ((0, 0), (0, 0), (0, pq)), constant_values=jnp.inf)
            .reshape(b * h, sq + pq) if pq else lse.reshape(b * h, sq))
    dvec = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)

    sq_p, sk_p = sq + pq, sk + pk
    kw = {"scale": scale, "causal": causal, "window": window,
          "block_q": block_q, "block_k": block_k, "off": sk - sq, "sk": sk}

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, a, bq: (bh, bq, 0))
    k_spec_kv = pl.BlockSpec((1, block_k, d), lambda bh, a, bq: (bh, a, 0))
    r_spec = pl.BlockSpec((1, block_q), lambda bh, a, bq: (bh, bq))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(b * h, sk_p // block_k, sq_p // block_q),
        in_specs=[q_spec, k_spec_kv, k_spec_kv, q_spec, r_spec, r_spec],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, a, bq: (bh, a, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, a, bq: (bh, a, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, sk_p, d), q.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bridge_flash_bwd_dkv",
    )(qf, kf, vf, dof, lsef, dvec)

    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bh, bq, a: (bh, bq, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, bq, a: (bh, a, 0))
    r_spec2 = pl.BlockSpec((1, block_q), lambda bh, bq, a: (bh, bq))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(b * h, sq_p // block_q, sk_p // block_k),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, bq, a: (bh, bq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bridge_flash_bwd_dq",
    )(qf, kf, vf, dof, lsef, dvec)

    dq = dq.reshape(b, h, sq_p, d)[:, :, :sq]
    dk = dk.reshape(b, h, sk_p, d)[:, :, :sk]
    dv = dv.reshape(b, h, sk_p, d)[:, :, :sk]
    return dq, dk, dv
