"""Reduce a profiler trace (``.xplane.pb``) to device and host numbers.

What is read:

- device planes (``/device:TPU:<n>``): the ``XLA Ops`` line is the
  device's operations, the ``XLA Modules`` line its jitted programs
  (named ``jit_<function>(<id>)``);
- the host plane (``/host:CPU``): the harness's ``chipbench.*`` spans,
  one line per thread; ``chipbench.window`` marks the traced window.

What comes out (:class:`Summary`): the window's length; per device the
union of its operation intervals inside the window (busy seconds); the
summed device time of each jitted program by name; the operations that
took most time; and the longest idle gaps of the first device, each
named by the innermost harness span open at its midpoint on any host
thread ("idle: no request in service" when none is).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
WINDOW_SPAN = "chipbench.window"
NO_SPAN = "idle: no request in service"
TOP = 10

Interval = Tuple[float, float]


@dataclass
class Summary:
    window_s: float
    busy_s: List[float]                              # per device
    program_s: Dict[str, float] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def program_seconds(self, function: str) -> Optional[float]:
        """Device seconds of the jitted ``function``, None if absent."""
        return self.program_s.get(function)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi)`` that ``busy`` (merged) does not cover."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _program_name(module: str) -> str:
    name = module.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def summarize(xspace) -> Summary:
    """Reduce a loaded ``jax.profiler.ProfileData``."""
    spans: List[Tuple[float, float, str]] = []
    window: Optional[Interval] = None
    devices = []
    for plane in xspace.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("chipbench."):
                        continue
                    a = ev.start_ns * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if ev.name == WINDOW_SPAN:
                        window = (a, b)
                    else:
                        spans.append((a, b, ev.name))
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("the trace has no device plane")
    lo, hi = window

    busy_s, program_s, op_s = [], {}, {}
    first_busy: List[Interval] = []
    for k, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        ops: List[Interval] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    iv = (a, a + ev.duration_ns * 1e-9)
                    if iv[1] <= lo or iv[0] >= hi:
                        continue
                    ops.append(iv)
                    c = _clip([iv], lo, hi)[0]
                    op_s[ev.name] = op_s.get(ev.name, 0.0) + c[1] - c[0]
            elif line.name == "XLA Modules":
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    c = _clip([(a, a + ev.duration_ns * 1e-9)], lo, hi)
                    if c:
                        name = _program_name(ev.name)
                        program_s[name] = (program_s.get(name, 0.0)
                                           + c[0][1] - c[0][0])
        merged = union(_clip(ops, lo, hi))
        busy_s.append(sum(b - a for a, b in merged))
        if k == 0:
            first_busy = merged

    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    named = []
    for a, b in idle:
        mid = (a + b) / 2
        open_ = [s for s in spans if s[0] <= mid < s[1]]
        label = max(open_, key=lambda s: s[0])[2] if open_ else NO_SPAN
        named.append((label, b - a))
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(hi - lo, busy_s, program_s, top_ops, named)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


__all__ = ["Summary", "find_xplane", "gaps", "load", "summarize", "union"]
