"""Compile the device programs for a described TPU v5e, without a chip.

The playback kernel at the chip smoke's plan and scoring shapes, and the
planned collectives at the four-chip phase's shapes, are compiled by the
TPU compiler for a ``v5e:2x2`` topology that is described, not attached.
Nothing runs: these tests catch what the chip's compiler would refuse (and
programs that would not fit a 16 GiB chip) before any chip time is spent.

The topology is described only inside a fixture: only one process may load
the TPU library, so a description made at import would break test
collection in every other worker.
"""
import os

import jax
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import chip_smoke
from repro.core import batchsim_jax, periodic_a2a
from repro.core.batchsim import compile_tape
from repro.core.cost_model import PAPER_DEFAULT

HBM_BYTES = 16 * 2**30      # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(topo.devices, (chip_smoke.AXIS,))


def _bytes_in_use(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


# B=32 covers the plan phase's 11-19 candidates per request (n=512, C=8,
# the Planner's sim_chunks); B=256 is the scoring phase's whole batch
# (n=1536, C=4), which play_certified splits into 4 buckets of 64; B=8 is
# the port-limited plan cell's largest padded bucket (n=256, C=8, S=8)
@pytest.mark.parametrize("B,n,C", [(32, 512, 8), (256, 1536, 4), (8, 256, 8)],
                         ids=["plan", "scoring", "ports"])
def test_playback_kernel_compiles_in_float64(one_chip, B, n, C):
    import jax.numpy as jnp

    S = compile_tape(periodic_a2a(n, 1)).S
    cm = PAPER_DEFAULT
    with jax.enable_x64(True):
        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        compiled = batchsim_jax._kernel().lower(
            arg((B, S), jnp.float64), arg((B, S), jnp.int64),
            arg((B, S), jnp.int64), arg((B, S), jnp.bool_),
            arg((B,), jnp.float64), arg((B,), jnp.int64),
            cm.alpha_s, cm.alpha_h, cm.beta, n=n, C=C).compile()
    assert "while" in compiled.as_text()
    assert 0 < _bytes_in_use(compiled) < HBM_BYTES


# The planned collectives compile at one eighth of the four-chip phase's
# buffers: the TPU compile time of these programs grows with the buffer
# (about 10 s for the full all-to-all and 35 s for the full all-reduce,
# against 1.5 s and 4 s at this size), while the lowered permute chain does
# not depend on it.
@pytest.mark.parametrize("name", ["all_to_all", "all_reduce"])
def test_planned_collective_compiles_on_four_chips(mesh, name):
    rows, d_model = chip_smoke.moe_dispatch_rows(mesh.devices.size)
    case = next(c for c in chip_smoke.collective_cases(
        mesh, a2a_rows=rows // 8, d_model=d_model,
        grad_elems=chip_smoke.GRAD_ELEMS // 8) if c["name"] == name)
    compiled = case["planned"].lower(case["arg"]).compile()
    assert "collective-permute" in compiled.as_text()
    assert _bytes_in_use(compiled) < HBM_BYTES
