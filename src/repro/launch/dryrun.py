import os
# Drop any inherited device-count flag (e.g. the CI matrix leg's 8-device
# XLA_FLAGS): the last occurrence wins in XLA, and the dry run needs 512.
_inherited = " ".join(
    tok for tok in os.environ.get("XLA_FLAGS", "").split()
    if not tok.startswith("--xla_force_host_platform_device_count"))
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + _inherited).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  - 16x16 single-pod mesh (256 chips) and 2x16x16 multi-pod mesh (512 chips);
  - train_4k lowers train_step (fwd+bwd+AdamW), prefill_32k lowers
    prefill, decode_32k / long_500k lower serve_step (one token against a
    full KV cache);
  - records memory_analysis(), cost_analysis() and the per-op collective
    byte counts parsed from the compiled HLO into a JSON report consumed by
    benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch rwkv6-3b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.data.pipeline import make_batch_specs  # noqa: E402
from repro.models import (SHAPES, decode_step, init_caches, init_params,  # noqa: E402
                          loss_fn, prefill)
from repro.models.sharding import activation_sharding  # noqa: E402
from repro.optim import adamw_init, adamw_update  # noqa: E402

from .mesh import batch_axes, make_production_mesh  # noqa: E402
from .shardings import (activation_rules, batch_shardings, cache_shardings,  # noqa: E402
                        param_shardings)

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


# --- HLO collective accounting ------------------------------------------------


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _dtype_bytes(name: str) -> int:
    return {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
            "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
            "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1}.get(name, 4)


def _first_shape_bytes(text: str) -> int:
    """Bytes of the result shape(s) at the start of an HLO instruction line."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _dtype_bytes(dt)
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, by op kind."""
    out = {k: {"bytes": 0, "count": 0} for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        # "%name = f32[...] all-reduce(...)" / fusion-wrapped starts too
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+)", ls)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.search(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)(?:-start|-done)?\(", rhs)
        if not opm:
            continue
        if "-done(" in rhs:  # avoid double counting start/done pairs
            continue
        kind = opm.group(1)
        head = rhs[:opm.start()]
        out[kind]["bytes"] += _first_shape_bytes(head)
        out[kind]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# --- step builders --------------------------------------------------------------


def build_train_step(cfg, mesh, seq_parallel: bool = False):
    rules = activation_rules(mesh, seq_parallel)

    def train_step(params, opt_state, batch):
        with activation_sharding(mesh, rules):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
        new_params, new_opt, om = adamw_update(grads, opt_state, params,
                                               lr=3e-4)
        metrics.update(om)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def build_prefill_step(cfg, mesh, max_seq):
    rules = activation_rules(mesh)

    def prefill_step(params, batch):
        with activation_sharding(mesh, rules):
            return prefill(cfg, params, batch, max_seq=max_seq)

    return prefill_step


def build_decode_step(cfg, mesh):
    rules = activation_rules(mesh)

    def serve_step(params, token, caches):
        with activation_sharding(mesh, rules):
            return decode_step(cfg, params, token, caches)

    return serve_step


# --- cell runner -----------------------------------------------------------------


VARIANTS = ("baseline", "logits-sharded", "moe-ep-data", "remat-dots",
            "remat-none", "kv-seq-sharded", "moe-vmap", "serve-tp-params",
            "seq-parallel")


def _apply_variant(cfg, variant: str):
    tweaks = {v.strip() for v in variant.split(",") if v.strip()}
    unknown = tweaks - set(VARIANTS)
    if unknown:
        raise ValueError(f"unknown variant(s) {unknown}; known: {VARIANTS}")
    if "remat-dots" in tweaks:
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    if "remat-none" in tweaks:
        cfg = dataclasses.replace(cfg, remat_policy="none")
    if "moe-vmap" in tweaks and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, vectorize_groups=True,
                                         group_size=128))
    return cfg, tweaks


def _lower_cell(cfg, shape, mesh, variant: str = "baseline"):
    """Lower one (config, shape) on a mesh; returns the Lowered object."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .mesh import batch_axes

    cfg, tweaks = _apply_variant(cfg, variant)
    moe_axis = "data" if "moe-ep-data" in tweaks else "model"
    fsdp = "serve-tp-params" not in tweaks

    params_shapes = jax.eval_shape(functools.partial(init_params, cfg),
                                   jax.random.PRNGKey(0))
    p_shard = param_shardings(mesh, params_shapes, moe_expert_axis=moe_axis,
                              fsdp=fsdp)
    batch_specs = make_batch_specs(cfg, shape)
    b_shard = batch_shardings(mesh, batch_specs)

    if shape.mode == "train":
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        o_shard = param_shardings(mesh, opt_shapes, moe_expert_axis=moe_axis)
        step = build_train_step(cfg, mesh,
                                seq_parallel="seq-parallel" in tweaks)
        with mesh:
            return jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
            ).lower(params_shapes, opt_shapes, batch_specs)
    if shape.mode == "prefill":
        step = build_prefill_step(cfg, mesh, max_seq=shape.seq_len)
        with mesh:
            return jax.jit(
                step, in_shardings=(p_shard, b_shard),
            ).lower(params_shapes, batch_specs)
    cache_shapes = jax.eval_shape(
        lambda: init_caches(cfg, shape.global_batch, shape.seq_len))
    c_shard = cache_shardings(mesh, cache_shapes,
                              kv_seq_shard="kv-seq-sharded" in tweaks)
    step = build_decode_step(cfg, mesh)
    logits_shard = None
    if "logits-sharded" in tweaks:
        # decode returns (logits (B, V), caches): keep logits distributed —
        # batch over (pod, data), vocab over model — instead of replicating
        baxes = batch_axes(mesh)
        vspec = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
        logits_shard = NamedSharding(mesh, P(baxes, vspec))
    with mesh:
        return jax.jit(
            step,
            in_shardings=(p_shard, b_shard["tokens"], c_shard),
            out_shardings=(logits_shard, c_shard),
        ).lower(params_shapes, batch_specs["tokens"], cache_shapes)


def _cell_metrics(compiled) -> dict:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops") or 0.0,
            "bytes_accessed": cost.get("bytes accessed") or 0.0,
            "collective_bytes": coll["total_bytes"],
            "collectives": coll}


def calibrate_depth(cfg, shape, mesh, variant: str = "baseline") -> dict:
    """Per-layer cost recovery: XLA cost analysis counts scan bodies ONCE, so
    lower unrolled 1-period and 2-period variants and extrapolate:
      P = X(2p) - X(p);  corrected = X(p) + P * (L/p - 1).
    """
    p = len(cfg.pattern)
    L = cfg.num_layers
    factor = L / p
    enc1 = max(1, round(cfg.num_encoder_layers / factor)) if cfg.enc_dec else 0
    small = dataclasses.replace(cfg, num_layers=p, unroll_layers=True,
                                num_encoder_layers=enc1)
    double = dataclasses.replace(cfg, num_layers=2 * p, unroll_layers=True,
                                 num_encoder_layers=2 * enc1)
    m1 = _cell_metrics(_lower_cell(small, shape, mesh, variant).compile())
    m2 = _cell_metrics(_lower_cell(double, shape, mesh, variant).compile())
    out = {}
    for k in ("flops", "bytes_accessed", "collective_bytes"):
        per_period = max(0.0, m2[k] - m1[k])
        out[k] = m1[k] + per_period * (factor - 1)
    out["per_period"] = {k: m2[k] - m1[k]
                         for k in ("flops", "bytes_accessed",
                                   "collective_bytes")}
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             calibrate: bool = True, variant: str = "baseline") -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    multi_pod = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    lowered = _lower_cell(cfg, shape, mesh, variant)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    n_dev = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "variant": variant,
        "devices": int(n_dev),
        "mode": shape.mode,
        "compile_seconds": round(t_compile, 1),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "memory": mem_info,
        "collectives": coll,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "hlo_lines": hlo.count("\n"),
    }
    if calibrate:
        # scan bodies are cost-counted once; recover per-layer costs from
        # unrolled 1-period / 2-period variants (see calibrate_depth)
        result["calibrated"] = calibrate_depth(cfg, shape, mesh, variant)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="baseline",
                    help="comma-separated tweaks: " + ", ".join(VARIANTS))
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for a, s in configs.cells():
            ok, why = configs.runnable(a, s)
            if ok:
                cells.append((a, s))
            else:
                print(f"SKIP {a} x {s}: {why}")
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch, shp in cells:
        for mk in meshes:
            tag = f"{arch}__{shp}__{mk}"
            if args.variant != "baseline":
                tag += "__" + args.variant.replace(",", "+")
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"CACHED {tag}")
                continue
            print(f"RUN {tag} ...", flush=True)
            try:
                res = run_cell(arch, shp, mk, variant=args.variant)
                status = "OK"
            except Exception as e:
                res = {"arch": arch, "shape": shp, "mesh": mk,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()}
                status = "FAIL"
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            extra = ""
            if status == "OK":
                extra = (f" flops={res['flops']:.3g}"
                         f" coll={res['collectives']['total_bytes']:.3g}B"
                         f" compile={res['compile_seconds']}s")
            print(f"{status} {tag}{extra}", flush=True)


if __name__ == "__main__":
    main()
