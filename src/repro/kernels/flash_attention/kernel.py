"""Tiled online-softmax attention kernel (pl.pallas_call + BlockSpec).

TPU-native design (DESIGN.md Section 6):
  grid = (batch * q_heads, num_q_blocks, num_k_blocks), k innermost and
  sequential ("arbitrary"); q/k/v tiles live in VMEM via BlockSpec; the
  running max/denominator/accumulator are VMEM scratch revisited across the
  k dimension (the canonical TPU flash pattern).

  VMEM working set per program:
    q tile (block_q, d) + k/v tiles (block_k, d) + acc (block_q, d) + stats.
  With block_q = block_k = 512 and d = 128 in f32 this is ~1.3 MB << 16 MB.
  MXU alignment: block sizes are multiples of 128.

Causal/sliding-window blocks that are fully masked are skipped via pl.when
(so the kernel's FLOP count matches the mask sparsity, e.g. ~1/2 for causal,
O(window/seq) for sliding-window — this is what makes long-context local
attention linear-time on TPU).

GQA is handled by the k/v index_map (query head h reads kv head h // group).
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, off: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global positions of this tile's queries/keys; ``off`` aligns the last
    # *real* query to the last real key (matching ref.attention_mask)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def masked_out() -> jax.Array:
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= k_pos <= q_pos + off
        if window is not None:
            mask &= k_pos > q_pos + off - window
        mask &= k_pos < sk  # key padding
        return mask

    def compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(masked_out(), s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    # Tile-level sparsity: skip fully-masked (q, k) tiles.
    run = True
    if causal:
        # tile has any k_pos <= q_pos + off  <=>  ki*bk <= qi*bq + bq-1 + off
        run = ki * block_k <= qi * block_q + block_q - 1 + off
    if window is not None:
        # tile has any k_pos > q_pos + off - window
        run_w = ki * block_k + block_k - 1 > qi * block_q + off - window
        run = jnp.logical_and(run, run_w) if causal else run_w

    if isinstance(run, bool):
        compute()
    else:
        pl.when(run)(compute)

    @pl.when(ki == nk - 1)
    def finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, :] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, :] = m_scr[...] + jnp.log(denom)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "block_q", "block_k",
                     "interpret"),
)
def flash_attention_fwd_lse(q, k, v, *, scale: float, causal: bool,
                            window: int | None, block_q: int = 512,
                            block_k: int = 512, interpret: bool = True):
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D) -> (out, logsumexp).

    out: (B, Hq, Sq, D); lse: (B, Hq, Sq) float32 (saved for the backward
    kernels)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    # pad sequence dims to block multiples (mask handles the padding keys;
    # padded queries are sliced off at the end)
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk

    qf = q.reshape(b * hq, sq_p, d)
    kf = k.reshape(b * hkv, sk_p, d)
    vf = v.reshape(b * hkv, sk_p, d)

    grid = (b * hq, sq_p // block_q, sk_p // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, off=sk - sq, sk=sk)  # real dims

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q), lambda bh, qi, ki: (bh, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * hq, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="bridge_flash_attention",
    )(qf, kf, vf)

    out = out.reshape(b, hq, sq_p, d)[:, :, :sq, :]
    lse = lse.reshape(b, hq, sq_p)[:, :, :sq]
    return out, lse


def flash_attention_fwd(q, k, v, *, scale: float, causal: bool,
                        window: int | None, block_q: int = 512,
                        block_k: int = 512, interpret: bool = True):
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D)."""
    return flash_attention_fwd_lse(
        q, k, v, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)[0]
