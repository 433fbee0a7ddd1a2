"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix names its ``driver`` (the loop in ``bench/drivers`` that offers it)
and the parameters of its load. Everything a run offers is drawn here from
``--seed`` and nowhere else, so the same seed gives the same load.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng(seed: int, stream: str) -> np.random.Generator:
    """A generator of its own for each use of the seed."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    words = [ord(ch) for ch in stream]
    return np.random.default_rng(np.random.SeedSequence([seed, *words]))


def plan_requests(mix: dict, seed: int) -> list[tuple[str, float]]:
    """(kind, m_bytes) of every request a closed-loop client will send.

    Requests come in rounds that hold every kind once; ``strata`` rounds
    make a block, in which every kind takes every stratum of log(m_bytes)
    once, log-uniform over the range. Kind k takes the strata in the order
    `spread_order` gives, shifted by k, so any run of rounds spreads each
    kind over the range; where in its stratum m_bytes lies follows a
    golden-ratio sequence over the blocks. So every seed offers the same
    sizes in the same rounds, and a window holds the same work whatever the
    seed: the seed draws the order of the kinds within each round. No two
    requests, and no request and warm-up size, share m_bytes, so every
    request misses the plan cache.
    """
    kinds = list(mix["kinds"])
    strata = int(mix["strata"])
    order = spread_order(strata)
    lo, hi = math.log(mix["m_bytes_min"]), math.log(mix["m_bytes_max"])
    gen = rng(seed, "plan_requests")
    seen = {float(m) for _, m in mix.get("warmup", [])}
    total = int(mix["requests"])
    out: list[tuple[str, float]] = []
    rnd = 0
    while len(out) < total:
        block, j = divmod(rnd, strata)
        for ki in gen.permutation(len(kinds)):
            stratum = order[(j + ki) % strata]
            index = (block * len(kinds) + ki) * strata + stratum
            u = (0.5 + index * GOLDEN) % 1.0
            m = float(math.exp(lo + (stratum + u) / strata * (hi - lo)))
            while m in seen:
                m = math.nextafter(m, math.inf)
            seen.add(m)
            out.append((kinds[ki], m))
        rnd += 1
    return out[:total]


def spread_order(n: int) -> list[int]:
    """0..n-1 in bit-reversed (van der Corput) order: 0, 4, 2, 6, 1, ... for
    n = 8, so every prefix is spread over the range."""
    bits = max(1, (n - 1).bit_length())
    rev = sorted(range(1 << bits),
                 key=lambda i: int(format(i, f"0{bits}b")[::-1], 2))
    return [i for i in rev if i < n]


def check_sample(n_done: int, size: int, seed: int) -> list[int]:
    """Indices of the completed requests whose answers are checked."""
    gen = rng(seed, "check_sample")
    size = min(size, n_done)
    return sorted(int(i) for i in gen.choice(n_done, size=size, replace=False))


def check_step(mix: dict, seed: int) -> int:
    """The step of a collective-step window whose results are checked."""
    lo, hi = mix["check_step_range"]
    return int(rng(seed, "check_step").integers(lo, hi))
