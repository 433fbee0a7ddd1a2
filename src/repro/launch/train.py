"""Training driver: GSPMD-sharded train loop with BRIDGE gradient sync,
checkpoint/restart, elastic resume and gradient compression.

Two gradient-sync modes (DESIGN.md S3/S5):
  gspmd  : loss is a global mean; XLA inserts the data-parallel all-reduce.
  bridge : per-shard local loss inside shard_map; gradients are summed
           explicitly with the paper's Bruck RS+AG collectives using
           schedules from the BRIDGE planner (repro.core), optionally int8-
           compressed with error feedback.  Used on pure-DP meshes.

Run small-scale (CPU):
  python -m repro.launch.train --arch rwkv6-3b --steps 20 --scale smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint import latest_step, restore_into, save
from repro.collectives import (bruck_all_reduce, compressed_all_reduce,
                               gradient_sync_plan, make_error_feedback_state)
from repro.data import SyntheticLM
from repro.models import init_params, loss_fn
from repro.models.sharding import activation_sharding
from repro.optim import adamw_init, adamw_update, cosine_warmup_schedule

from .mesh import make_mesh
from .shardings import activation_rules, param_shardings


@dataclasses.dataclass
class TrainConfig:
    arch: str = "rwkv6-3b"
    scale: str = "smoke"             # smoke (scaled_down) | full
    steps: int = 20
    batch_size: int = 8              # global
    seq_len: int = 64
    lr: float = 3e-4
    warmup: int = 10
    grad_sync: str = "gspmd"         # gspmd | bridge | bridge-compressed
    checkpoint_dir: str | None = None
    checkpoint_every: int = 10
    mesh_shape: tuple = ()
    mesh_axes: tuple = ()
    seed: int = 0


def model_config(tc: TrainConfig):
    cfg = configs.get(tc.arch)
    if tc.scale == "smoke":
        cfg = cfg.scaled_down()
        cfg = dataclasses.replace(cfg, dtype="float32")
    return cfg


def make_train_step(cfg, tc: TrainConfig, mesh):
    lr = cosine_warmup_schedule(tc.lr, tc.warmup, tc.steps)
    rules = activation_rules(mesh)

    if tc.grad_sync == "gspmd":
        def step(params, opt_state, batch, ef):
            with activation_sharding(mesh, rules):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
            params, opt_state, om = adamw_update(grads, opt_state, params, lr)
            metrics.update(om)
            metrics["loss"] = loss
            return params, opt_state, metrics, ef
        return step

    # explicit BRIDGE sync on a pure-DP axis ('data'); params replicated
    axis = "data"
    n_dp = mesh.shape[axis]
    compressed = tc.grad_sync == "bridge-compressed"

    def local_grads(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
        return loss, metrics, grads

    def step(params, opt_state, batch, ef):
        from jax.sharding import PartitionSpec as P

        def shard_fn(params, batch, ef):
            loss, metrics, grads = local_grads(params, batch)
            if compressed:
                grads, ef2 = compressed_all_reduce(grads, ef, axis)
            else:
                plan = gradient_sync_plan(
                    n_dp, sum(g.size * g.dtype.itemsize
                              for g in jax.tree.leaves(grads)))
                if plan.impl == "bruck":
                    grads = jax.tree.map(
                        lambda g: bruck_all_reduce(g, axis, plan.rs_schedule,
                                                   plan.ag_schedule), grads)
                else:
                    grads = jax.tree.map(
                        lambda g: jax.lax.psum(g, axis), grads)
                ef2 = ef
            grads = jax.tree.map(lambda g: g / n_dp, grads)
            loss = jax.lax.pmean(loss, axis)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
            return loss, metrics, grads, ef2

        pspec_batch = jax.tree.map(lambda _: P(axis), batch)
        # check_vma=False: outputs *are* replicated (explicit Bruck
        # all-reduce), but the ppermute chain defeats static inference.
        loss, metrics, grads, ef = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), pspec_batch, P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(params, batch, ef)
        params, opt_state, om = adamw_update(grads, opt_state, params, lr)
        metrics.update(om)
        metrics["loss"] = loss
        return params, opt_state, metrics, ef

    return step


def train(tc: TrainConfig, progress=print):
    cfg = model_config(tc)
    if tc.mesh_shape:
        mesh = make_mesh(tuple(tc.mesh_shape), tuple(tc.mesh_axes))
    else:
        mesh = make_mesh((jax.device_count(),), ("data",))
    data = SyntheticLM(cfg.vocab_size, tc.seq_len, seed=tc.seed)

    params = init_params(cfg, jax.random.PRNGKey(tc.seed))
    opt_state = adamw_init(params)
    ef = (make_error_feedback_state(params)
          if tc.grad_sync == "bridge-compressed" else {})

    start = 0
    if tc.checkpoint_dir:
        last = latest_step(tc.checkpoint_dir)
        if last is not None:
            state = restore_into(tc.checkpoint_dir,
                                 {"params": params, "opt": opt_state},
                                 step=last)
            params, opt_state = state["params"], state["opt"]
            start = last
            progress(f"resumed from step {start}")

    p_shard = param_shardings(mesh, jax.eval_shape(lambda: params))
    params = jax.device_put(params, p_shard)
    step_fn = jax.jit(make_train_step(cfg, tc, mesh), donate_argnums=(0, 1))

    losses = []
    for step in range(start, tc.steps):
        # one stream per example: the global batch is identical for any mesh
        # shape / world size (elastic resume and straggler backup workers
        # recompute bit-identical data; DESIGN.md S5)
        host_batch = data.global_batch(step, tc.batch_size, 1)
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        t0 = time.time()
        params, opt_state, metrics, ef = step_fn(params, opt_state, batch, ef)
        loss = float(metrics["loss"])
        losses.append(loss)
        progress(f"step {step:5d} loss {loss:.4f} "
                 f"gnorm {float(metrics['grad_norm']):.3f} "
                 f"dt {time.time() - t0:.2f}s")
        if tc.checkpoint_dir and (step + 1) % tc.checkpoint_every == 0:
            save(tc.checkpoint_dir, step + 1,
                 {"params": jax.device_get(params),
                  "opt": jax.device_get(opt_state)})
    return params, opt_state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--scale", default="smoke")
    ap.add_argument("--grad-sync", default="gspmd")
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()
    tc = TrainConfig(arch=args.arch, steps=args.steps,
                     batch_size=args.batch_size, seq_len=args.seq_len,
                     scale=args.scale, grad_sync=args.grad_sync,
                     checkpoint_dir=args.checkpoint_dir)
    _, _, losses = train(tc)
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
