"""Worker executed in a subprocess with XLA_FLAGS host-device-count set.

Validates every collective in repro.collectives against jax.lax oracles on a
real multi-device (host-platform) mesh.  Prints 'ALL-OK' on success.
"""
import os
import sys

N = int(sys.argv[1]) if len(sys.argv) > 1 else 8
# Drop any inherited device-count flag (e.g. from the CI matrix leg that runs
# the whole suite under 8 host devices): the last occurrence wins in XLA, and
# this worker's N must control the mesh size.
_inherited = " ".join(
    tok for tok in os.environ.get("XLA_FLAGS", "").split()
    if not tok.startswith("--xla_force_host_platform_device_count"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N} {_inherited}").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.collectives import (bridge_all_reduce, bruck_all_gather,  # noqa: E402
                               bruck_all_reduce, bruck_all_to_all,
                               bruck_reduce_scatter, compressed_all_reduce,
                               make_error_feedback_state, ring_all_gather,
                               ring_all_reduce, ring_reduce_scatter)
from repro.core import PAPER_DEFAULT, plan  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

assert jax.device_count() == N, jax.device_count()
mesh = make_mesh((N,), ("ring",))
AXIS = "ring"


def smap(f, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


def check(name, got, want, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol,
                               rtol=1e-5, err_msg=name)
    print(f"ok {name}")


key = jax.random.PRNGKey(0)

# ---- all-to-all --------------------------------------------------------------
x = jax.random.normal(key, (N, N, 4, 3))  # global: (devices, rows-per-device...)
oracle = smap(lambda a: jax.lax.all_to_all(a, AXIS, 0, 0), P(AXIS), P(AXIS))(
    x.reshape(N * N, 4, 3))
got = smap(lambda a: bruck_all_to_all(a, AXIS), P(AXIS), P(AXIS))(
    x.reshape(N * N, 4, 3))
check("bruck_all_to_all", got, oracle)

# ---- reduce-scatter ----------------------------------------------------------
x = jax.random.normal(key, (N, N, 6))
want_full = x.sum(axis=0)  # (N, 6) block j at device j


def rs_run(fn):
    return smap(lambda a: fn(a, AXIS)[None], P(AXIS), P(AXIS))(x.reshape(N * N, 6))


check("bruck_reduce_scatter", rs_run(bruck_reduce_scatter), want_full)
check("ring_reduce_scatter", rs_run(ring_reduce_scatter), want_full)

rs_sched = plan("rs", N, 6 * 4.0, PAPER_DEFAULT).schedule
got = smap(lambda a: bruck_reduce_scatter(a, AXIS, rs_sched)[None], P(AXIS),
           P(AXIS))(x.reshape(N * N, 6))
check("bruck_reduce_scatter(schedule)", got, want_full)

# ---- all-gather ----------------------------------------------------------------
x = jax.random.normal(key, (N, 5))
want = jnp.broadcast_to(x[None], (N, N, 5)).reshape(N * N, 5)


def ag_run(fn, *args):
    return smap(lambda a: fn(a[0], AXIS, *args), P(AXIS), P(AXIS))(x)


check("bruck_all_gather", ag_run(bruck_all_gather), want)
check("ring_all_gather", ag_run(ring_all_gather), want)
ag_sched = plan("ag", N, 5 * 4.0, PAPER_DEFAULT).schedule
check("bruck_all_gather(schedule)", ag_run(bruck_all_gather, ag_sched), want)

# ---- all-reduce -----------------------------------------------------------------
x = jax.random.normal(key, (N, 7, 11))  # deliberately not divisible by N
want = jnp.broadcast_to(x.sum(0)[None], (N, 7, 11)).reshape(N * 7, 11)


def ar_run(fn, **kw):
    return smap(lambda a: fn(a.reshape(7, 11), AXIS, **kw).reshape(7, 11),
                P(AXIS), P(AXIS))(x.reshape(N * 7, 11))


check("ring_all_reduce", ar_run(ring_all_reduce), want)
check("bruck_all_reduce", ar_run(bruck_all_reduce), want)
got = smap(lambda a: bridge_all_reduce(a.reshape(7, 11), AXIS, N).reshape(7, 11),
           P(AXIS), P(AXIS))(x.reshape(N * 7, 11))
check("bridge_all_reduce", got, want)

# ---- compressed all-reduce with error feedback ----------------------------------
g = jax.random.normal(key, (N, 33)) * 3.0
want_sum = g.sum(0)


def comp(a):
    grads = {"w": a.reshape(33)}
    ef = make_error_feedback_state(grads)
    out1, ef = compressed_all_reduce(grads, ef, AXIS)
    # second round on the same grads: error feedback corrects round-1 error
    out2, ef = compressed_all_reduce(grads, ef, AXIS)
    return jnp.stack([out1["w"], out2["w"]])


got = smap(lambda a: comp(a)[None], P(AXIS), P(AXIS))(g)
got = np.asarray(got)  # (N, 2, 33) stacked per device, all identical
err1 = np.abs(got[0, 0] - np.asarray(want_sum)).max()
rel = err1 / np.abs(np.asarray(want_sum)).max()
assert rel < 0.05, f"int8 quantization error too large: {rel}"
print(f"ok compressed_all_reduce (rel err {rel:.4f})")

# round-2 output = quantized(g + e): error feedback means avg of round1+round2
# approximates 2*sum better than 2*round1 alone
err_fb = np.abs(got[0, 0] + got[0, 1] - 2 * np.asarray(want_sum)).max()
assert err_fb <= 2 * err1 + 1e-6, (err_fb, err1)
print("ok error_feedback")

print("ALL-OK")
