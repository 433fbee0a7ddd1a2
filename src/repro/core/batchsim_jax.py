"""JAX ``jit``/``vmap`` backend for certified tape playback.

`batchsim._play` is a NumPy loop nest: Python iterates steps and hop streams,
NumPy vectorizes the ``[B, n, C]`` grid inside each hop.  At n in the
thousands the per-hop Python dispatch and the guards' bookkeeping dominate;
this module lowers the *certified* subset of that playback to XLA:

  - the `ScheduleTape` stacks (``counts``/``g_step``/``hops``/``changed``)
    become device arrays with static shapes per ``(n, C)`` bucket,
  - the per-lane step loop becomes a `lax.scan` over S steps (carry: the
    per-port busy-until vector ``F`` and last-receive vector ``recv``),
  - the hop streams become a `lax.while_loop`, chunks an inner `lax.scan`,
  - `jax.vmap` maps the lane over the batch axis and `jax.jit` compiles the
    whole playback once per distinct ``(B, S, n, C)`` shape.

Soundness gate.  The kernel has *no* canonical-order guards and *no* skew
knobs — it is only called for lanes holding a static fast-path certificate
(`repro.analysis.certifier`), which proves the guards could not have tripped
and implies the lane is uniform (no ``link_speed`` / ``payload_scale``).
Uncertified lanes never reach this module: `batchsim.batch_run` keeps routing
them through the guarded NumPy playback with the scalar-oracle fallback.

Exactness.  Everything runs in float64 (``jax.enable_x64(True)`` is
entered around each playback call, so the x64 mode never leaks into other
jax users in the process) and the kernel performs the same float ops in the
same order as `_play`: service ``f = max(f, arrival) + tau`` per chunk,
``tau = (nb / C) * beta``, gather by ``(port - g) % n``, ``+ alpha_h`` per
hop, ``+ alpha_s`` per injection, ``delta_eff`` charged at rewiring
boundaries.  On the CPU the result is bit-identical to the NumPy engine.
The TPU emulates float64, so there it is not: on a TPU v5e the worst
relative difference against NumPy was 9.6e-13 over the planner's n=512
candidate sets and 1.6e-13 over 256 lanes at n=1536 (`chip_smoke.py`),
inside the 1e-6 the backend promises.  Playback is deterministic
run-to-run on both (the differential suite and the chip smoke pin it).

Hop bucketing.  ``vmap`` runs every lane of a call through each step's
``while_loop`` as often as the call's longest lane there.  `play_certified`
sorts lanes by total hops and, from 64 lanes up, splits the batch into at
most `max_buckets` contiguous buckets of at least `min_bucket_size` lanes,
each jitted at its own shape.  A planner's candidate set is smaller (10-22
lanes at n = 1024), so it plays as one bucket and its static-schedule lane
sets every lane's trip count: at n = 1024 about 0.91 of the chunk-services
the kernel runs are padding (`chunk_services`, carried by the
``repro.playback`` span; PERF.md has the chip's reading).  Bucketing or
ordering a set's lanes by hop count is the change to measure before a
rewrite of the kernel.

Importing this module never requires jax (`repro.collectives._compat`
guards the probe); `jax_available()` tells callers whether the backend can
actually run.  See docs/batch_engine.md for the full performance model.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.collectives._compat import HAS_JAX, require_jax
from .cost_model import CostModel
from .spans import span

# trace_count increments only when XLA traces (= compiles) the kernel for a
# new shape; calls counts every playback dispatch and lanes the certified
# lanes those dispatches played.  The jit-cache test pins trace_count flat
# across repeated same-shape batches.
_STATS = {"trace_count": 0, "calls": 0, "lanes": 0}


def jax_available() -> bool:
    """True when the jax import probe succeeded (backend can run)."""
    return HAS_JAX


def compile_stats() -> dict:
    """Snapshot of {'trace_count', 'calls', 'lanes'} — kernel
    (re)compilations, playback dispatches and lanes played since import /
    `reset_compile_stats`."""
    return dict(_STATS)


def reset_compile_stats() -> None:
    for key in _STATS:
        _STATS[key] = 0


@functools.lru_cache(maxsize=1)
def _kernel():
    """Build (once) the jitted, vmapped playback kernel.

    Deferred so importing this module never touches jax; the first certified
    playback pays the closure construction, every later call reuses the same
    jit object and therefore XLA's per-shape compile cache.
    """
    jax = require_jax("the JAX batch backend (backend='jax')")
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnames=("n", "C"))
    def play(nb, g, h, changed, delta_eff, alpha_s, alpha_h, beta, n, C):
        # Python side effect: fires at trace time only, so this counts XLA
        # compilations, not dispatches
        _STATS["trace_count"] += 1
        ports = jnp.arange(n)

        def lane(nb_l, g_l, h_l, ch_l, de_l):
            def step(carry, xs):
                F, recv = carry
                nbk, gk, hk, chk = xs
                # rewiring boundary: every port stalls delta_eff (k=0 never
                # charges — the host zeroes changed[:, 0])
                F = F + jnp.where(chk, de_l, 0.0)
                inj = recv + alpha_s          # recv is 0 at k=0 -> alpha_s
                tau = (nbk / C) * beta        # uniform: no speed/scale skew
                idx = (ports - gk) % n
                arr = jnp.broadcast_to(inj[None, :], (C, n))

                def cond(st):
                    return st[0] < hk

                def hop(st):
                    j, arr_h, F_h, recv_h = st

                    def chunk(f, a_c):
                        f = jnp.maximum(f, a_c) + tau
                        return f, f

                    f, comp = lax.scan(chunk, F_h, arr_h)
                    nxt = comp[:, idx] + alpha_h
                    recv_h = jnp.where(j + 1 >= hk, nxt[C - 1], recv_h)
                    return j + 1, nxt, f, recv_h

                _, _, F, recv = lax.while_loop(
                    cond, hop, (jnp.zeros((), dtype=h_l.dtype), arr, F, recv))
                return (F, recv), recv.max()

            (F, recv), sd = lax.scan(
                step, (jnp.zeros(n), jnp.zeros(n)), (nb_l, g_l, h_l, ch_l))
            return recv, sd, F

        return jax.vmap(lane)(nb, g, h, changed, delta_eff)

    return play


def _bucket_indices(hops: np.ndarray, max_buckets: int,
                    min_bucket_size: int) -> list[np.ndarray]:
    """Contiguous lane buckets of ascending total hop count.

    The stable sort keeps equal-work lanes in input order; small batches stay
    in one bucket (a bucket per handful of lanes would just multiply compile
    cost without shortening anyone's while_loop).
    """
    order = np.argsort(hops.sum(axis=1), kind="stable")
    k = max(1, min(int(max_buckets), len(order) // max(1, int(min_bucket_size))))
    return [idx for idx in np.array_split(order, k) if idx.size]


def chunk_services(hops: np.ndarray, n: int, C: int) -> tuple[int, int]:
    """(needed, run) chunk-services of one bucket's ``[lanes, S]`` hops.

    needed = n * C * sum of every lane's hops: what the lanes' playback has
    to serve, `BatchFabricResult.chunks_moved` summed over them.  run =
    n * C * lanes * sum over steps k of max over lanes of ``hops[:, k]``:
    under ``vmap`` every lane of the bucket goes round step k's while_loop
    as often as the bucket's longest lane there, so 1 - needed / run of
    what the kernel serves is padding.
    """
    h = np.asarray(hops, dtype=np.int64)
    return (n * C * int(h.sum()),
            n * C * h.shape[0] * int(h.max(axis=0, initial=0).sum()))


def play_certified(*, n: int, C: int, cm: CostModel, nb_step: np.ndarray,
                   g_step: np.ndarray, hops: np.ndarray, changed: np.ndarray,
                   delta_eff: np.ndarray, max_buckets: int = 4,
                   min_bucket_size: int = 32):
    """Guard-free playback of a certified-lane batch on the XLA backend.

    Inputs are the same ``[B, S]`` tape stacks `batchsim.batch_run` builds
    (``nb_step`` per-node payload bytes, ``g_step`` link offsets, ``hops``
    per-step hop counts, ``changed`` rewiring-boundary mask, per-lane
    ``delta_eff``).  Every lane MUST hold a static fast-path certificate —
    the caller (`batch_run`) enforces this; uniformity is what licenses
    dropping the per-port speed/scale arrays and the runtime guards.

    Returns ``(node_done [B, n], step_done [B, S], port_free [B, n])`` as
    NumPy float64 arrays in the original lane order (bucketing is internal).
    """
    jax = require_jax("the JAX batch backend (backend='jax')")

    B, S = nb_step.shape
    play = _kernel()
    node_done = np.empty((B, n))
    step_done = np.empty((B, S))
    port_free = np.empty((B, n))
    nb = np.ascontiguousarray(nb_step, dtype=np.float64)
    g = np.ascontiguousarray(g_step, dtype=np.int64)
    h = np.ascontiguousarray(hops, dtype=np.int64)
    ch = np.ascontiguousarray(changed, dtype=bool)
    ch[:, 0] = False          # step 0 never charges delta (x[0] == 0)
    de = np.ascontiguousarray(delta_eff, dtype=np.float64)
    _STATS["calls"] += 1
    _STATS["lanes"] += B
    # x64 as a context, not a global flag: float64 playback without leaking
    # the mode into unrelated jax users in the same process
    with jax.enable_x64(True):
        for idx in _bucket_indices(h, max_buckets, min_bucket_size):
            needed, run = chunk_services(h[idx], n, C)
            with span("playback", lanes=int(idx.size),
                      chunk_services=needed, chunk_services_run=run):
                nd, sd, pf = play(nb[idx], g[idx], h[idx], ch[idx], de[idx],
                                  cm.alpha_s, cm.alpha_h, cm.beta, n=n, C=C)
                node_done[idx] = np.asarray(nd)
                step_done[idx] = np.asarray(sd)
                port_free[idx] = np.asarray(pf)
    return node_done, step_done, port_free
