"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The 'pod' axis can run pipeline stages instead of data parallelism: each
device along the axis holds one contiguous stage of layers; microbatches
stream through with a `ppermute(+1)` activation hand-off per tick —
`n_micro + n_stages - 1` ticks total (the classic GPipe schedule; bubble
fraction (S-1)/(M+S-1)).

This is a composable utility deliberately independent of the model zoo: any
`stage_fn(stage_params, x) -> x` works.  Used in tests on a CPU mesh, and
available to the launcher for cross-pod pipelining (DESIGN.md S5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _shift_perm(n: int, offset: int) -> list[tuple[int, int]]:
    return [(i, (i + offset) % n) for i in range(n)]


def pipeline_apply(stage_fn, stage_params, x_micro, axis_name: str):
    """Run microbatches through pipeline stages laid out on `axis_name`.

    Must be called inside shard_map.  Args (per device):
      stage_params : this device's stage parameters
      x_micro      : (M, mb, ...) all microbatches (only stage 0 reads them)
    Returns (M, mb, ...) final-stage outputs (valid on the last stage; other
    stages return zeros), suitable for psum/gather by the caller.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    ticks = m + n - 1

    out = jnp.zeros_like(x_micro)
    carry = jnp.zeros(mb_shape, x_micro.dtype)
    # mark the loop state as device-varying over the pipeline axis (the loop
    # body mixes in axis_index / ppermute results, which are varying)
    out = jax.lax.pcast(out, (axis_name,), to="varying")
    carry = jax.lax.pcast(carry, (axis_name,), to="varying")

    def tick(t, state):
        out, carry = state
        # stage 0 ingests microbatch t (if in range); others take the carry
        mb_in = jax.lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        x_in = jnp.where(idx == 0, mb_in, carry)
        y = stage_fn(stage_params, x_in)
        # last stage writes its finished microbatch (t - (n-1))
        done_idx = t - (n - 1)
        write = (idx == n - 1) & (done_idx >= 0)
        upd = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(done_idx, 0, m - 1), axis=0)
        out = jnp.where(write, upd, out)
        # hand activations to the next stage
        carry = jax.lax.ppermute(y, axis_name, _shift_perm(n, 1))
        return out, carry

    out, _ = jax.lax.fori_loop(0, ticks, tick, (out, carry))
    return out


def run_pipeline(mesh, axis_name, stage_fn, all_stage_params, x, n_micro):
    """Convenience wrapper: shard params by stage, split x into microbatches,
    run the pipeline, return outputs gathered at the caller.

    all_stage_params: pytree with leading dim = n_stages.
    x: (batch, ...) with batch % n_micro == 0.
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    assert b % n_micro == 0
    x_micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    def body(stage_params, xm):
        # stage_params arrives with a leading dim of 1 (its stage slice)
        stage_params = jax.tree.map(lambda p: p[0], stage_params)
        out = pipeline_apply(stage_fn, stage_params, xm, axis_name)
        # broadcast final-stage outputs to every stage for uniform return
        return jax.lax.psum(out, axis_name)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    )(all_stage_params, x_micro)
    return out.reshape(b, *out.shape[2:])
