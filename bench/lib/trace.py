"""Capture a profiler trace of the measured window and reduce it.

The harness marks the window and its own calls into the service with
``jax.profiler.TraceAnnotation`` spans named ``bench:*``. They land on
the host lines of the same trace, on the profiler's clock, so each idle
gap of the device can be put down to what the host was doing in it.

Reduction, per device plane (``/device:<PLATFORM>:<n>``), inside the
window span:

* busy: the union of the intervals of the events on the device's op
  line; ``busy_s`` is its length, averaged over the devices;
* op seconds: each op name's summed duration (clipped to the window);
* idle gaps: the complement of busy, each gap named by the innermost
  ``bench:*`` span on every host line that covers its midpoint, and
  summed by that name.
"""
from __future__ import annotations

import glob
import os
import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
#: the line of a device plane that holds one event per executed op
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


@contextmanager
def capture(log_dir: str):
    """Profile the body; yields a list that holds the ``.xplane.pb``
    path once the body has ended."""
    import jax.profiler as jp
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0     # no per-call Python events
    opts.host_tracer_level = 1       # annotations and JAX's own spans
    found: List[str] = []
    jp.start_trace(log_dir, profiler_options=opts)
    try:
        yield found
    finally:
        jp.stop_trace()
        found.extend(sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)[-1:])


@dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                       # mean over devices
    op_seconds: Dict[str, float]        # summed over devices
    idle_gaps: Dict[str, float] = field(default_factory=dict)

    def seconds_of(self, pattern: str) -> float:
        """Total seconds of the ops whose name holds ``pattern``."""
        return sum(v for k, v in self.op_seconds.items() if pattern in k)

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.op_seconds.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.idle_gaps.items(),
                                          key=lambda kv: -kv[1])[:n]]


def op_name(hlo: str) -> str:
    """``%rlc_mergejoin.1 = s32[...] custom-call(...)`` -> the op's own
    name, ``rlc_mergejoin.1`` (TPU traces name ops by their HLO text)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged ``(start, end)`` rows of possibly overlapping intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, dtype=np.float64)


def reduce(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to the window's numbers.

    Raises ``ValueError`` when the trace holds no window span."""
    host_spans: List[_Line] = []
    window: Optional[Tuple[float, float]] = None
    devices = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
            if spans:
                host_spans.append(_Line(spans))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = window
    op_seconds: Dict[str, float] = {}
    busy = []
    gaps: Dict[str, float] = {}
    for plane in devices:
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, w0)
                b = min(ev.start_ns + ev.duration_ns, w1)
                if b <= a:
                    continue
                iv.append((a, b))
                name = op_name(ev.name)
                op_seconds[name] = op_seconds.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        for a, b in edges:
            if b > a:
                name = _host_activity(host_spans, (a + b) / 2)
                gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    n_dev = max(len(devices), 1)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, devices=len(devices),
        busy_s=sum(busy) / n_dev, op_seconds=op_seconds,
        idle_gaps={k: v / n_dev for k, v in gaps.items()})


class _Line:
    """The ``bench:*`` spans of one host thread, which nest, with each
    span's nearest enclosing span."""

    def __init__(self, spans):
        spans.sort(key=lambda sp: (sp[0], -sp[1]))
        self.start = np.array([sp[0] for sp in spans])
        self.end = np.array([sp[1] for sp in spans])
        self.name = [sp[2] for sp in spans]
        self.parent = np.full(len(spans), -1)
        stack: List[int] = []
        for i in range(len(spans)):
            while stack and self.end[stack[-1]] <= self.start[i]:
                stack.pop()
            self.parent[i] = stack[-1] if stack else -1
            stack.append(i)

    def innermost(self, at: float) -> Optional[str]:
        i = int(np.searchsorted(self.start, at, side="right")) - 1
        while i >= 0 and self.end[i] <= at:
            i = int(self.parent[i])
        return self.name[i] if i >= 0 else None


def _host_activity(lines: List[_Line], at: float) -> str:
    """Innermost ``bench:*`` span covering ``at`` on each host line."""
    names = [n for n in (ln.innermost(at) for ln in lines) if n]
    return "+".join(sorted(names)) if names else "none"
