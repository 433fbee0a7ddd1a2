"""`chip_smoke.py` off the chip, and the compile-cache helper it calls.

The script refuses to run without a TPU, so its phases are driven here
directly, at small sizes on the CPU: the checks they make on the chip are
the same checks, against the same NumPy references.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from benchmarks import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_smoke_refuses_without_a_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_plan_phase_matches_numpy_planner():
    lines = list(chip_smoke.plan_phase(n=64))
    assert [ln.split(":")[0] for ln in lines] == [
        f"plan {k}" for k in chip_smoke.PLAN_KINDS]
    assert all("worst_rel_diff=" in ln and "cold_s=" in ln for ln in lines)


def test_plan_phase_fails_when_the_device_kernel_never_runs(monkeypatch):
    """A plan that NumPy answered instead of the device is a failure."""
    from repro.core import batchsim

    real = batchsim._resolve_backend
    monkeypatch.setattr(batchsim, "_resolve_backend",
                        lambda backend, **kw: real("numpy", **kw))
    with pytest.raises(chip_smoke.SmokeError, match="kernel calls"):
        list(chip_smoke.plan_phase(n=64, kinds=("a2a",)))


def test_scoring_phase_matches_numpy_engine():
    line = chip_smoke.scoring_phase(n=96, lanes_target=16, hop_cap=60)
    assert "lanes=16 certified_lanes=16 backend=jax" in line
    assert "bit_stable=True" in line


def test_four_chip_phase_on_four_host_devices():
    code = "\n".join([
        "import jax, chip_smoke",
        "assert len(jax.devices()) == 4",
        "for line in chip_smoke.four_chip_phase(",
        "        jax.devices(), a2a_rows=16, d_model=32, grad_elems=4096):",
        "    print(line)",
    ])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    names = [ln.split(":")[0] for ln in proc.stdout.splitlines()]
    assert names == [f"four-chip {c}" for c in
                     ("all_to_all", "reduce_scatter", "all_gather",
                      "all_reduce")]


def test_last_line_is_the_driver_contract(monkeypatch, capsys):
    """On a TPU the last line is exactly the ok/device object."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_check", lambda count=None: device)
    monkeypatch.setattr(chip_smoke, "plan_phase", lambda: iter(["plan a2a"]))
    monkeypatch.setattr(chip_smoke, "scoring_phase", lambda: "scoring")
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "dir")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}


# --- compile-cache helper ------------------------------------------------------


@pytest.fixture
def jax_cache_config():
    """Restore JAX's cache settings after the helper changed them."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_the_repo(monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_writes_only_where_the_variable_says(tmp_path):
    where = tmp_path / "cache"
    code = "\n".join([
        "import jax, jax.numpy as jnp",
        "from benchmarks.compile_cache import enable_compile_cache",
        "print(enable_compile_cache())",
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()",
    ])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(where),
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(where)]
    assert any(where.iterdir())
