"""Worker executed in a subprocess with 8 XLA host devices.

    python tests/_bruck_a2a_worker.py '<cases as JSON>'

The cases are tests/test_bruck_a2a_multidevice.py's: ``world_sizes``,
``dtypes``, ``trailing`` (name -> shape), ``lowered_sizes`` and
``lowered_trailing``.  Runs `bruck_all_to_all` against
`jax.lax.all_to_all(x, axis, 0, 0)` on meshes of the first n devices for
every world size, dtype and trailing shape, and lowers it (bfloat16 blocks
of ``lowered_trailing``) at each of ``lowered_sizes``.  Prints one JSON line:

    {"exact": {"<case id>": true | false | "<error>"},
     "lowered": {"<n>": "<StableHLO text>"}}
"""
import json
import os
import sys

DEVICES = 8
_inherited = " ".join(
    tok for tok in os.environ.get("XLA_FLAGS", "").split()
    if not tok.startswith("--xla_force_host_platform_device_count"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={DEVICES} {_inherited}").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.collectives import bruck_all_to_all  # noqa: E402

AXIS = "ring"


def program(mesh, fn):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(AXIS),
                                 out_specs=P(AXIS)))


def data(rng, shape, dtype: str) -> np.ndarray:
    """Random blocks with signed zeros, infinities and NaNs among floats."""
    if dtype == "int32":
        info = np.iinfo(np.int32)
        return rng.integers(info.min, info.max, shape, dtype=np.int32,
                            endpoint=True)
    x = rng.standard_normal(shape).astype(np.float32)
    flat = x.reshape(-1)
    flat[:4] = [-0.0, np.inf, -np.inf, np.nan]
    rng.shuffle(flat)
    return x.astype(jnp.dtype(dtype))


def bits(a) -> np.ndarray:
    """The array's raw bits, so NaN payloads and -0.0 compare exactly."""
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def main(cases: dict) -> None:
    assert jax.device_count() == DEVICES, jax.device_count()
    rng = np.random.default_rng(0)
    exact, lowered = {}, {}
    for n in cases["world_sizes"]:
        mesh = Mesh(np.asarray(jax.devices()[:n]), (AXIS,))
        bruck = program(mesh, lambda a: bruck_all_to_all(a, AXIS))
        xla = program(mesh, lambda a: jax.lax.all_to_all(a, AXIS, 0, 0))
        for dtype in cases["dtypes"]:
            for name, trailing in cases["trailing"].items():
                x = data(rng, (n * n, *trailing), dtype)
                case = f"n{n}-{dtype}-{name}"
                try:
                    got, want = bruck(x), xla(x)
                    exact[case] = bool(
                        got.shape == want.shape and got.dtype == want.dtype
                        and np.array_equal(bits(got), bits(want)))
                except Exception as e:  # reported per case by the test
                    exact[case] = repr(e)
        if n in cases["lowered_sizes"]:
            spec = jax.ShapeDtypeStruct((n * n, *cases["lowered_trailing"]),
                                        jnp.bfloat16)
            lowered[str(n)] = bruck.lower(spec).as_text()
    print(json.dumps({"exact": exact, "lowered": lowered}))


if __name__ == "__main__":
    sys.exit(main(json.loads(sys.argv[1])))
