"""`bruck_all_to_all` on 2-8 devices: exact, and lowered without copies.

Every case runs in one subprocess with 8 XLA host devices
(tests/_bruck_a2a_worker.py), so the device count never leaks into this
pytest process.  Two properties:

- bit-equality with `jax.lax.all_to_all(x, axis, 0, 0)` at world sizes that
  are and are not powers of two, in bfloat16, float32 and int32, with
  trailing shapes of rank 1 and 2;
- the lowered program moves whole blocks only: it has no gather and no
  scatter, and its collective permutes carry exactly the Bruck rounds'
  bytes, round k (offset 2^k) the blocks of the slots whose bit k is set.
"""
import json
import math
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORLD_SIZES = (2, 3, 4, 5, 6, 8)        # powers of two and not
DTYPES = ("bfloat16", "float32", "int32")
TRAILING = {"rank1": (3,), "rank2": (4, 3)}
LOWERED_SIZES = (4, 6, 8)
LOWERED_TRAILING = (5, 3)               # bfloat16 blocks of the lowering
PERMUTE = re.compile(
    r'"stablehlo\.collective_permute"\(.*source_target_pairs = '
    r'dense<\[\[(\d+), (\d+)\].*: \(tensor<([\dx]+)x(\w+)>\)')
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "i32": 4}


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    cases = {"world_sizes": WORLD_SIZES, "dtypes": DTYPES,
             "trailing": TRAILING, "lowered_sizes": LOWERED_SIZES,
             "lowered_trailing": LOWERED_TRAILING}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_bruck_a2a_worker.py"),
         json.dumps(cases)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n", WORLD_SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rank", sorted(TRAILING))
def test_bruck_all_to_all_is_bit_equal_to_xla(results, n, dtype, rank):
    assert results["exact"][f"n{n}-{dtype}-{rank}"] is True


def permute_bytes_by_offset(text: str, n: int) -> dict[int, int]:
    """Bytes of the collective permutes' operands, by ring offset."""
    by_offset: dict[int, int] = {}
    for src, dst, dims, elem in PERMUTE.findall(text):
        offset = (int(dst) - int(src)) % n
        size = math.prod(int(d) for d in dims.split("x")) * ITEMSIZE[elem]
        by_offset[offset] = by_offset.get(offset, 0) + size
    return by_offset


@pytest.mark.parametrize("n", LOWERED_SIZES)
def test_lowered_bruck_all_to_all_sends_blocks_as_they_are(results, n):
    text = results["lowered"][str(n)]
    for op in ("gather", "scatter"):
        assert not re.search(rf"stablehlo\.(dynamic_)?{op}\b", text), op
    block = math.prod(LOWERED_TRAILING) * ITEMSIZE["bf16"]
    rounds = {2**k: sum((j >> k) & 1 for j in range(1, n)) * block
              for k in range(math.ceil(math.log2(n)))}
    assert permute_bytes_by_offset(text, n) == rounds
