"""Dry-run comparison of gradient-allreduce lowerings (the paper's technique
as it appears in the compiled artifact).

Lowers bruck / ring / psum allreduce for a gradient payload on an abstract
8-device ring (no real devices needed) and counts collective-permute ops and
moved bytes from the lowered text — this is the 'profile' the Section Perf
hillclimb reads (no wall-clock on CPU; see ROOFLINE notes in EXPERIMENTS.md).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.collectives import bruck_all_reduce, ring_all_reduce
from repro.core import PAPER_DEFAULT, plan


def count_collectives(text: str) -> dict:
    return {
        "collective_permute": len(re.findall(r"collective_permute|collective-permute", text)),
        "all_reduce": len(re.findall(r"all_reduce|all-reduce", text)),
        "all_gather": len(re.findall(r"all_gather|all-gather", text)),
        "reduce_scatter": len(re.findall(r"reduce_scatter|reduce-scatter", text)),
    }


def lower_allreduce_variants(n: int = 8, nbytes: int = 1 << 20) -> dict:
    mesh = AbstractMesh((n,), ("data",),
                        axis_types=(jax.sharding.AxisType.Auto,))
    elems = nbytes // 4
    x = jax.ShapeDtypeStruct((elems,), jnp.float32)
    m = float(nbytes)
    rs = plan("rs", n, m, PAPER_DEFAULT).schedule
    ag = plan("ag", n, m, PAPER_DEFAULT).schedule

    variants = {
        "bruck": lambda v: bruck_all_reduce(v, "data"),
        "bruck_scheduled": lambda v: bruck_all_reduce(v, "data", rs, ag),
        "ring": lambda v: ring_all_reduce(v, "data"),
        "psum": lambda v: jax.lax.psum(v, "data"),
    }
    out = {}
    for name, fn in variants.items():
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"), check_vma=False)
        lowered = jax.jit(mapped).lower(
            jax.ShapeDtypeStruct((n * elems,), jnp.float32))
        out[name] = count_collectives(lowered.as_text())
        out[name]["steps_modeled"] = (
            2 * (n - 1) if name == "ring"
            else 2 * (n - 1).bit_length() if "bruck" in name else None)
    return out
