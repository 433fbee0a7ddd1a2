"""Profile a span of a run, and reduce the profile to what the metrics read.

`Profiler` wraps ``jax.profiler`` around part of a window and reduces the
``.xplane.pb`` it writes (into a temporary directory, removed afterwards) to
a small dict, the reduced trace::

    {"window": [start_ns, end_ns],          # the host span "bench.window"
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, duration_ns], ...],      # XLA Ops
                  "modules": [[name, start_ns, duration_ns], ...]}, # XLA Modules
                 ...],
     "host_spans": [[name, start_ns, duration_ns], ...]}   # "bench.*" spans

Every reduction below works on that dict alone, so it is checked on a small
recorded one (``bench/tests/data``). Device busy time is the union of the
intervals in which an operation ran, clipped to the window, averaged over
the devices. Programs are found by the name of their jitted function; a
name that is not in the trace raises `MissingEvent` rather than reading 0.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
TOP = 10


class MissingEvent(LookupError):
    """A program or span the reduction looks for is not in the trace."""


class Profiler:
    """Profile from `start` to `stop`; `reduce` then reads the profile.

    Disabled, every method does nothing and `reduce` returns None.
    """

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._dir = None
        self._span = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self._dir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        """Stop profiling; the profile is read later, by `reduce`."""
        if not self.enabled or self._span is None:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self._span is not None

    def reduce(self) -> dict | None:
        """Read the profile written, remove it, and return it reduced."""
        if not self.enabled or self._dir is None:
            return None
        try:
            paths = glob.glob(f"{self._dir}/**/*.xplane.pb", recursive=True)
            if len(paths) != 1:
                raise MissingEvent(f"expected one .xplane.pb, found {paths}")
            return load_xplane(paths[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


def span(name: str):
    """A host span of the benchmark's own (``bench.<name>``) in the trace."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def load_xplane(path: str) -> dict:
    """Reduce one ``.xplane.pb`` to the dict this module works on."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append({
                "name": plane.name,
                "ops": _events(lines.get(OP_LINE)),
                "modules": _events(lines.get(MODULE_LINE))})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e[0].startswith("bench."))
    windows = [e for e in host if e[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise MissingEvent(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    _, start, dur = windows[0]
    devices.sort(key=lambda d: d["name"])
    return {"window": [start, start + dur], "devices": devices,
            "host_spans": [e for e in host if e[0] != WINDOW_SPAN]}


def _events(line) -> list:
    if line is None:
        return []
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for e in line.events]


# --- reductions ---------------------------------------------------------------


def window_s(trace: dict) -> float:
    start, end = trace["window"]
    return (end - start) / 1e9


def _merged(events: list, window) -> tuple[np.ndarray, np.ndarray]:
    """Union of the events' intervals inside ``window``: (starts, ends)."""
    lo, hi = window
    if not events:
        return np.zeros(0), np.zeros(0)
    arr = np.asarray([e[1:] for e in events], dtype=np.float64)
    s = np.clip(arr[:, 0], lo, hi)
    e = np.clip(arr[:, 0] + arr[:, 1], lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    if s.size == 0:
        return s, e
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    group = np.cumsum(new) - 1
    starts = s[new]
    ends = np.zeros(starts.size)
    np.maximum.at(ends, group, e)
    return starts, ends


def _busy_lines(dev: dict) -> list:
    """The events that mark the device busy: its ops, else its modules."""
    return dev["ops"] or dev["modules"]


def busy_s(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace["devices"]:
        raise MissingEvent("the trace holds no TPU device plane")
    total = 0.0
    for dev in trace["devices"]:
        starts, ends = _merged(_busy_lines(dev), trace["window"])
        total += float(np.sum(ends - starts))
    return total / 1e9 / len(trace["devices"])


def idle_share(trace: dict) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def module_base(name: str) -> str:
    """A module event's name without the id XLA appends, ``jit_f(12)``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def program_seconds(trace: dict, function: str) -> tuple[float, float]:
    """(seconds per device, calls per device) of the jitted ``function``.

    Its module events are ``jit_<function>``; both numbers are averaged
    over the devices. Raises `MissingEvent` where no device ran it.
    """
    want = f"jit_{function}"
    secs, calls = [], []
    for dev in trace["devices"]:
        mine = [e for e in dev["modules"] if module_base(e[0]) == want]
        secs.append(sum(e[2] for e in mine) / 1e9)
        calls.append(len(mine))
    if not any(calls):
        raise MissingEvent(f"no device ran the program {want}")
    n = len(trace["devices"])
    return sum(secs) / n, sum(calls) / n


def op_family(name: str) -> str:
    """What kind of op an XLA Ops event is, from its HLO text.

    ``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``; a custom call adds
    its target: ``custom-call:X64SplitLow``.
    """
    head = name.split(" = ", 1)[0].lstrip("%")
    family = re.sub(r"(\.(\d+|clone))+$", "", head)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{family}:{target.group(1)}" if target else family


def _self_times(events: list):
    """(name, seconds) of each event less the events nested inside it.

    Events of one line nest (a loop's body ops lie inside the loop op), so
    each op's own time is its duration minus that of its direct children.
    """
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: list[int] = []
    for i in order:
        start = events[i][1]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= events[i][2]
        stack.append(i)
    return [(e[0], max(0.0, t) / 1e9) for e, t in zip(events, own)]


def breakdown(trace: dict) -> dict:
    """Top device ops by their own time, and idle gaps by host span.

    A gap between busy intervals is put down to the shortest ``bench.*``
    host span that covers its middle, or to ``(no bench span)``. Seconds are
    averaged over the devices.
    """
    n = max(1, len(trace["devices"]))
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    family: dict[str, str] = {}
    spans = sorted(trace["host_spans"], key=lambda e: -e[2])
    owners = [s[0] for s in spans] + ["(no bench span)"]
    lo, hi = trace["window"]
    for dev in trace["devices"]:
        for name, secs in _self_times(_busy_lines(dev)):
            key = family.get(name) or family.setdefault(name, op_family(name))
            ops[key] = ops.get(key, 0.0) + secs / n
        starts, ends = _merged(_busy_lines(dev), trace["window"])
        a = np.concatenate([[lo], ends])
        b = np.concatenate([starts, [hi]])
        keep = b > a
        a, b = a[keep], b[keep]
        mid = (a + b) / 2
        # longest spans first, so the shortest span covering a gap wins
        owner = np.full(mid.size, len(spans))
        for i, (_, s0, d0) in enumerate(spans):
            owner[(mid >= s0) & (mid <= s0 + d0)] = i
        secs = np.bincount(owner, weights=b - a, minlength=len(owners))
        for i, v in enumerate(secs):
            if v > 0:
                gaps[owners[i]] = gaps.get(owners[i], 0.0) + float(v) / 1e9 / n
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}
