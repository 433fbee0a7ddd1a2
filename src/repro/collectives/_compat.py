"""Guarded jax import probe.

Importing this module must never raise, so a CPU-only install without jax
can still import the pure-NumPy core: `repro.core.batchsim` and the JAX
batch backend consult `HAS_JAX` / `require_jax()` instead of importing jax
at module scope and letting jax import errors leak into the core path.
`require_jax` raises a clear `ImportError` only at the call that actually
needs jax.
"""
from __future__ import annotations

try:  # the probe itself must never raise at import time
    import jax
    HAS_JAX = True
    JAX_IMPORT_ERROR: Exception | None = None
except Exception as exc:  # pragma: no cover - exercised on jax-less installs
    jax = None  # type: ignore[assignment]
    HAS_JAX = False
    JAX_IMPORT_ERROR = exc


def require_jax(feature: str = "this feature"):
    """Return the jax module, raising an actionable error when absent.

    The JAX batch backend funnels through this, so a jax-less install fails
    at the *call* that genuinely needs jax with a message naming the
    feature, never at import time.
    """
    if not HAS_JAX:  # pragma: no cover - exercised on jax-less installs
        raise ImportError(
            f"{feature} requires jax, which failed to import "
            f"({JAX_IMPORT_ERROR!r}); install jax[cpu] or use the NumPy "
            f"backend") from JAX_IMPORT_ERROR
    return jax
