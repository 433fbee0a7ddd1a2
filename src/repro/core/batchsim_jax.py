"""JAX ``jit``/``vmap`` backend for certified tape playback.

`batchsim._play` is a NumPy loop nest: Python iterates steps and hop streams,
NumPy vectorizes the ``[B, n, C]`` grid inside each hop.  At n in the
thousands the per-hop Python dispatch and the guards' bookkeeping dominate;
this module lowers the *certified* subset of that playback to XLA:

  - the `ScheduleTape` stacks (``counts``/``stride``/``hops``/``changed``)
    and the per-lane block size become device arrays with static shapes per
    ``(n, C)`` and hop bucket,
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
``tau = (nb / C) * beta``, gather by ``(port - stride) % n`` on a block's
first node and ``(port - 1) % n`` on the others (every node is a block's
first on a full-port fabric, where the stride is the link offset g),
``+ alpha_h`` per hop, ``+ alpha_s`` per injection, ``delta_eff`` charged
at rewiring boundaries.  On the CPU the result is bit-identical to the NumPy engine.
The TPU emulates float64, so there it is not: on a TPU v5e the worst
relative difference against NumPy was 9.6e-13 over the planner's n=512
candidate sets and 1.6e-13 over 256 lanes at n=1536 (`chip_smoke.py`),
inside the 1e-6 the backend promises.  Playback is deterministic
run-to-run on both (the differential suite and the chip smoke pin it).

Hop buckets.  ``vmap`` runs every lane of a call through each step's
``while_loop`` as often as the call's longest lane there, so a lane played
beside a longer one pads.  `play_certified` sorts the lanes by total hops
and `partition` cuts that order into the contiguous buckets whose predicted
device time (`_CALL_S`, `_TRIP_S`, `_CHUNK_SERVICE_S`: a per-call, a
per-trip and a per-chunk-service cost, fitted on a TPU v5e) sums to the
least.  At n = 1024 every planner candidate set holds one static-schedule
lane of 1023 hops and lanes of 10-134; the partition plays the static lane
alone and the rest in a few buckets of like hop counts, which took the
share of padding in the chunk-services the kernel runs from 0.91 to 0.05
(PERF.md).  A blocked lane (Section 3.7) plays its electrical hops as
hops too, so its trips and chunk-services are counted from the hops it
really runs.  Each bucket's lane count is padded up a fixed ladder (1, 2, 4,
8, then multiples of 8, `padded_lanes`) with inert lanes, so the compiled
shapes are few and do not depend on the request; the partition is charged
for the padding.  Every bucket is dispatched, the one of the most hops
first so the host dispatches the rest while the device plays it, before
any is fetched.

Backend choice.  `prefers_device` predicts a certified set's playback on
both engines: on the device from the same bucket cost model, on the host
from NumPy's cost per chunk-service measured on the host of that device
kind (`_HOST_CHUNK_SERVICE_S`).  `batchsim._resolve_backend` plays the set
where it finishes first; a kind with no measurement keeps its work floor.

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
# new shape; calls counts `play_certified` calls, buckets the jitted `play`
# dispatches they made, lanes the certified lanes they played (padding
# lanes not counted) and blocked_lanes those of them on a port-limited
# (Section 3.7) fabric; hops sums the lanes' hops and blocked_hops what the
# block floor adds to them over a full-port fabric.  The jit-cache test
# pins trace_count flat across repeated same-shape batches.
_STATS = {"trace_count": 0, "calls": 0, "buckets": 0, "lanes": 0,
          "blocked_lanes": 0, "hops": 0, "blocked_hops": 0}


def jax_available() -> bool:
    """True when the jax import probe succeeded (backend can run)."""
    return HAS_JAX


def compile_stats() -> dict:
    """Snapshot of {'trace_count', 'calls', 'buckets', 'lanes',
    'blocked_lanes', 'hops', 'blocked_hops'} — kernel (re)compilations,
    playback calls, the kernel dispatches they made, the certified lanes
    played, the blocked ones among them, and their hops in all and added by
    the block floor, since import / `reset_compile_stats`."""
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
    def play(nb, stride, h, changed, delta_eff, block, alpha_s, alpha_h,
             beta, n, C):
        # Python side effect: fires at trace time only, so this counts XLA
        # compilations, not dispatches
        _STATS["trace_count"] += 1
        ports = jnp.arange(n)

        def lane(nb_l, s_l, h_l, ch_l, de_l, b_l):
            # a block's first node gathers over the optical circuit, the
            # others over the electrical link (padding lanes: block 0 -> 1);
            # on a full-port fabric (blocks of 1) every node is a block's first
            first = ports % jnp.maximum(b_l, 1) == 0

            def step(carry, xs):
                F, recv = carry
                nbk, sk, hk, chk = xs
                # rewiring boundary: every port stalls delta_eff (k=0 never
                # charges — the host zeroes changed[:, 0])
                F = F + jnp.where(chk, de_l, 0.0)
                inj = recv + alpha_s          # recv is 0 at k=0 -> alpha_s
                tau = (nbk / C) * beta        # uniform: no speed/scale skew
                idx = (ports - jnp.where(first, sk, 1)) % n
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
                step, (jnp.zeros(n), jnp.zeros(n)), (nb_l, s_l, h_l, ch_l))
            return recv, sd, F

        return jax.vmap(lane)(nb, stride, h, changed, delta_eff, block)

    return play


# Time one jitted `play` call adds to a playback, as the partition predicts it:
#   _CALL_S + _TRIP_S * trips + _CHUNK_SERVICE_S * n * C * lanes_run * trips
# where trips = sum over steps k of the bucket's longest hops[:, k] (the
# while_loop trips every lane of the call goes round) and lanes_run is the
# bucket's padded lane count.  Fitted on a TPU v5e ("TPU v5 lite") from a
# traced run of the n = 1024 planner's candidate sets: the device time of
# module jit_play over 37 bucket shapes, L_pad 1-8 and 11-1023 trips (0.128
# ms + 3.55 us a trip + 1.2546 ns a chunk-service run, within 11% of every
# shape and 2% of the median one), plus the 0.125 ms of host time each
# further bucket adds to the `repro.playback` span.  Properties of the
# device, like `batchsim._JAX_AUTO_MIN_WORK`, not user settings; PERF.md
# has the fit.
_CALL_S = 0.25e-3
_TRIP_S = 3.55e-6
_CHUNK_SERVICE_S = 1.2546e-9

# Host seconds per chunk-service of the NumPy playback (`batchsim._play`) of
# certified lanes, by the device kind JAX reports (the key of
# bench/peaks.json): what `prefers_device` weighs the device's predicted
# time against.  Measurements of the host that holds the chip, not settings.
# "TPU v5 lite": the v5e host, 74.7 ns over n = 256 blocked candidate sets
# (8 chunks, 8 steps, ~1.1e6 chunk-services a set) in a traced run of the
# mems256.plan-miss cell with NumPy playing them; timing `batch_run(...,
# backend="numpy")` of the same sets there read 61-78 ns (PERF.md).
_HOST_CHUNK_SERVICE_S = {"TPU v5 lite": 74.7e-9}

# padded lane counts up to 8 (index = lanes); above 8, the next multiple of 8
_SMALL_LADDER = np.array([0, 1, 2, 4, 4, 8, 8, 8, 8])


def padded_lanes(lanes):
    """The lane count a bucket of ``lanes`` is compiled and played at: the
    next rung of the ladder 1, 2, 4, 8, 16, 24, 32, ... (scalar or array)."""
    lanes = np.asarray(lanes)
    return np.where(lanes > 8, -(-lanes // 8) * 8,
                    _SMALL_LADDER[np.minimum(lanes, 8)])


def _predicted_seconds(lanes_run, trips, n: int, C: int):
    """Predicted device time of one `play` call (the model above)."""
    return (_CALL_S + _TRIP_S * np.asarray(trips)
            + _CHUNK_SERVICE_S * n * C * np.asarray(lanes_run) * trips)


def partition(hops: np.ndarray, n: int, C: int) -> list[np.ndarray]:
    """Hop buckets of a ``[lanes, S]`` hop matrix: lane indices, one array a
    `play` call.

    Lanes are sorted by total hops (stable, so equal-work lanes keep input
    order) and cut into the contiguous buckets whose predicted device time,
    summed, is least: a shortest path over the cut points, which lie only
    between runs of equal total hops (O(G^2 * S) for G distinct totals).
    Each bucket is charged its padded lane count, since padding lanes run
    every trip under ``vmap`` as well.
    """
    return _partition(hops, n, C)[0]


def predicted_seconds(hops: np.ndarray, n: int, C: int) -> float:
    """Predicted device time of playing ``hops`` in `partition`'s buckets."""
    return _partition(hops, n, C)[1]


def _partition(hops: np.ndarray, n: int, C: int):
    """(buckets, predicted seconds); the last few hop matrices are kept, so
    the backend choice and the playback that follows share one solve (the
    cost constants are part of the key)."""
    h = np.ascontiguousarray(hops, dtype=np.int64)
    return _solve_partition(h.tobytes(), h.shape, n, C,
                            (_CALL_S, _TRIP_S, _CHUNK_SERVICE_S))


@functools.lru_cache(maxsize=16)
def _solve_partition(raw: bytes, shape: tuple, n: int, C: int, _constants):
    hops = np.frombuffer(raw, dtype=np.int64).reshape(shape)
    total = hops.sum(axis=1)
    order = np.argsort(total, kind="stable")
    cuts = np.flatnonzero(np.diff(total[order])) + 1
    bounds = np.concatenate([[0], cuts, [order.size]])
    # per-step maxima of each run of equal totals, [G, S]
    run_max = np.maximum.reduceat(hops[order], bounds[:-1], axis=0)
    G = run_max.shape[0]
    best = np.full(G + 1, np.inf)
    best[0] = 0.0
    prev = np.zeros(G + 1, dtype=np.int64)
    for i in range(G):
        # buckets [bounds[i], bounds[j]) for every j > i
        trips = np.maximum.accumulate(run_max[i:], axis=0).sum(axis=1)
        cost = best[i] + _predicted_seconds(
            padded_lanes(bounds[i + 1:] - bounds[i]), trips, n, C)
        better = cost < best[i + 1:]
        best[i + 1:][better] = cost[better]
        prev[i + 1:][better] = i
    buckets = []
    j = G
    while j:
        i = prev[j]
        buckets.append(order[bounds[i]:bounds[j]])
        j = i
    return buckets[::-1], float(best[G])


@functools.lru_cache(maxsize=1)
def _device_kind() -> str:
    return require_jax("the JAX batch backend").devices()[0].device_kind


def prefers_device(hops: np.ndarray, n: int, C: int) -> bool | None:
    """Whether certified lanes of ``hops`` ([lanes, S]) are predicted to
    play faster on the device than in NumPy on its host; None where the
    device kind has no measured host cost (the caller's floor decides)."""
    host = _HOST_CHUNK_SERVICE_S.get(_device_kind())
    if host is None:
        return None
    work = float(C) * n * float(np.asarray(hops).sum())
    return predicted_seconds(hops, n, C) < host * work


def chunk_services(hops: np.ndarray, n: int, C: int,
                   lanes_run: int | None = None) -> tuple[int, int]:
    """(needed, run) chunk-services of one bucket's ``[lanes, S]`` hops.

    needed = n * C * sum of every lane's hops: what the lanes' playback has
    to serve, `BatchFabricResult.chunks_moved` summed over them.  run =
    n * C * lanes_run * sum over steps k of max over lanes of ``hops[:, k]``:
    under ``vmap`` every lane of the bucket, padding lanes included
    (``lanes_run``, default the bucket's own lanes), goes round step k's
    while_loop as often as the bucket's longest lane there, so 1 - needed /
    run of what the kernel serves is padding.
    """
    h = np.asarray(hops, dtype=np.int64)
    if lanes_run is None:
        lanes_run = h.shape[0]
    return (n * C * int(h.sum()),
            n * C * int(lanes_run) * int(h.max(axis=0, initial=0).sum()))


def _padded(a: np.ndarray, idx: np.ndarray, lanes_run: int) -> np.ndarray:
    """Rows ``idx`` of ``a``, then zero rows up to ``lanes_run``: inert
    padding lanes (no hops, no payload, offset 0) whose results are
    dropped."""
    out = np.zeros((lanes_run,) + a.shape[1:], dtype=a.dtype)
    out[:idx.size] = a[idx]
    return out


def play_certified(*, n: int, C: int, cm: CostModel, nb_step: np.ndarray,
                   stride: np.ndarray, hops: np.ndarray, changed: np.ndarray,
                   delta_eff: np.ndarray, block: np.ndarray,
                   blocked_hops: int = 0):
    """Guard-free playback of a certified-lane batch on the XLA backend.

    Inputs are the same ``[B, S]`` tape stacks `batchsim.batch_run` builds
    (``nb_step`` per-node payload bytes, ``stride`` optical hop strides —
    the link offsets on a full-port fabric —, ``hops`` per-step hop counts,
    ``changed`` rewiring-boundary mask, per-lane ``delta_eff`` and
    ``block`` size, 1 on a full-port fabric), and ``blocked_hops``, the hops the
    block floor adds to them, for the span and the counters.  Every lane
    MUST hold a static fast-path certificate —
    the caller (`batch_run`) enforces this; uniformity is what licenses
    dropping the per-port speed/scale arrays and the runtime guards.

    The lanes are split into the hop buckets `partition` chooses, each
    padded up the `padded_lanes` ladder with inert lanes (no hops, no
    payload); every bucket is dispatched, the one of the most hops first,
    before any result is fetched.
    Returns ``(node_done [B, n], step_done [B, S], port_free [B, n])`` as
    NumPy float64 arrays in the original lane order.
    """
    jax = require_jax("the JAX batch backend (backend='jax')")

    B, S = nb_step.shape
    play = _kernel()
    node_done = np.empty((B, n))
    step_done = np.empty((B, S))
    port_free = np.empty((B, n))
    nb = np.ascontiguousarray(nb_step, dtype=np.float64)
    st = np.ascontiguousarray(stride, dtype=np.int64)
    h = np.ascontiguousarray(hops, dtype=np.int64)
    ch = np.array(changed, dtype=bool)
    ch[:, 0] = False          # step 0 never charges delta (x[0] == 0)
    de = np.ascontiguousarray(delta_eff, dtype=np.float64)
    bl = np.ascontiguousarray(block, dtype=np.int64)
    with span("playback", lanes=B, blocked_hops=int(blocked_hops)) as sp:
        buckets = partition(h, n, C)
        runs = [int(padded_lanes(idx.size)) for idx in buckets]
        needed = run = 0
        for idx, lanes_run in zip(buckets, runs):
            nd_b, rn_b = chunk_services(h[idx], n, C, lanes_run)
            needed += nd_b
            run += rn_b
        sp.set_metadata(buckets=len(buckets), lanes_run=sum(runs),
                        chunk_services=needed, chunk_services_run=run)
        _STATS["calls"] += 1
        _STATS["buckets"] += len(buckets)
        _STATS["lanes"] += B
        _STATS["blocked_lanes"] += int((bl > 1).sum())
        _STATS["hops"] += int(h.sum())
        _STATS["blocked_hops"] += int(blocked_hops)
        # x64 as a context, not a global flag: float64 playback without
        # leaking the mode into unrelated jax users in the same process
        with jax.enable_x64(True):
            # the bucket of the most hops first, so the device plays it
            # while the host dispatches the rest; then one fetch of all
            pending = [(idx, play(*(_padded(a, idx, lanes_run)
                                    for a in (nb, st, h, ch, de, bl)),
                                  cm.alpha_s, cm.alpha_h, cm.beta, n=n, C=C))
                       for idx, lanes_run in zip(buckets[::-1], runs[::-1])]
            fetched = jax.device_get([out for _, out in pending])
        for (idx, _), (nd, sd, pf) in zip(pending, fetched):
            node_done[idx] = nd[:idx.size]
            step_done[idx] = sd[:idx.size]
            port_free[idx] = pf[:idx.size]
    return node_done, step_done, port_free
