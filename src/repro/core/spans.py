"""Named host spans of the planning path, recorded by ``jax.profiler``.

`span(name, **args)` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` that carries ``args`` as stats of the trace event.  With no
profiler running it records nothing and costs well under a microsecond; under
``jax.profiler.trace`` / ``start_trace`` the spans land in the same
``.xplane.pb`` as the device ops, on the same clock.  Spans of one request
nest on the caller's thread, so nesting gives each span its parent.  Counts
known only at the end of a span are attached with the annotation's
``set_metadata(**args)``.

There is no switch and no buffer here: the profiler is the recorder, and it
is off unless someone starts it.  A profiler only runs in a process that has
imported jax, so until then a span is an inert stand-in and planning never
imports jax for its spans (the NumPy core stays importable without jax, and
`repro.collectives._compat` cannot be imported from ``core`` without a
cycle).  The spans and their arguments are listed in docs/tracing.md.
"""
from __future__ import annotations

import contextlib
import sys


class _NoSpan(contextlib.nullcontext):
    """A span while jax is not imported: enters as itself, records nothing."""

    def __init__(self):
        super().__init__(self)

    def set_metadata(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **args):
    """A ``repro.<name>`` trace span carrying ``args`` (a context manager)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(f"repro.{name}", **args)
