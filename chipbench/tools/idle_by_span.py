"""Split a traced window's device idle time by the program's spans.

    python3 chipbench/tools/idle_by_span.py <log_dir | .xplane.pb>

Reads a profiler trace of a benchmark window (the ``chipbench.window``
span marks it) and puts the first device's idle time down to host spans:
on each host thread each instant belongs to the innermost span open
there; idle time is shared equally among the threads inside a span at
that instant, so the parts add up to the idle seconds, and goes to
"idle: no request in service" when no thread is.  The program's
``repro.*`` spans are used when the trace has any, else the harness's
``chipbench.*``.  Prints one JSON line: ``window_s``, ``idle_s``,
``idle_s_by_span`` (largest first) and ``idle_gaps``, the ten longest
gaps, each named by its largest part.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import traces  # noqa: E402

PROGRAM_PREFIX = "repro."
Piece = Tuple[float, float, str]


def innermost(spans: List[Piece]) -> List[Piece]:
    """One thread's spans (which nest) cut into pieces, each instant
    named by the innermost span open at it."""
    out: List[Piece] = []
    stack: List[Tuple[float, str]] = []             # (end, name)
    t = float("-inf")

    def emit(a: float, b: float, name: str) -> None:
        if b > a:
            out.append((a, b, name))

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            end, outer = stack.pop()
            emit(t, end, outer)
            t = max(t, end)
        if stack:
            emit(t, a, stack[-1][1])
        t = max(t, a)
        stack.append((b, name))
    while stack:
        end, outer = stack.pop()
        emit(t, end, outer)
        t = max(t, end)
    return out


def split_idle(threads: List[List[Piece]],
               idle: List[traces.Interval]) -> List[Dict[str, float]]:
    """Per idle interval (sorted, disjoint), its seconds by span."""
    events = sorted((t, step, name)
                    for pieces in threads for a, b, name in pieces
                    for t, step in ((a, 1), (b, -1)))
    out: List[Dict[str, float]] = [{} for _ in idle]
    active: Dict[str, int] = {}
    k, j = 0, 0
    lo = idle[0][0] if idle else 0.0
    t_prev = lo
    for t, step, name in events + [(float("inf"), 0, "")]:
        x, y = max(t_prev, lo), t
        while j < len(idle) and idle[j][1] <= x:
            j += 1
        i = j
        while y > x and i < len(idle) and idle[i][0] < y:
            ov = min(y, idle[i][1]) - max(x, idle[i][0])
            if ov > 0:
                parts = out[i]
                if k == 0:
                    parts[traces.NO_SPAN] = parts.get(traces.NO_SPAN,
                                                      0.0) + ov
                else:
                    for n, c in active.items():
                        parts[n] = parts.get(n, 0.0) + ov * c / k
            i += 1
        if step:
            active[name] = active.get(name, 0) + step
            if not active[name]:
                del active[name]
            k += step
        t_prev = max(t_prev, t)
    return out


def split(xspace) -> Dict[str, object]:
    """The window's idle seconds by span, and its longest gaps named."""
    lines: List[List[Piece]] = []
    window = None
    devices = []
    for plane in xspace.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if ev.name == traces.WINDOW_SPAN:
                        window = (a, b)
                    elif ev.name.startswith((PROGRAM_PREFIX, "chipbench.")):
                        spans.append((a, b, ev.name))
                if spans:
                    lines.append(spans)
        elif traces.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"the trace has no {traces.WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("the trace has no device plane")
    lo, hi = window
    first = sorted(devices, key=lambda p: p.name)[0]
    ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
           for line in first.lines if line.name == "XLA Ops"
           for ev in line.events]
    idle = traces.gaps(traces.union(traces._clip(ops, lo, hi)), lo, hi)
    program = any(n.startswith(PROGRAM_PREFIX)
                  for spans in lines for _a, _b, n in spans)
    prefix = PROGRAM_PREFIX if program else "chipbench."
    parts = split_idle([innermost([s for s in spans
                                   if s[2].startswith(prefix)])
                        for spans in lines], idle)
    by_span: Dict[str, float] = {}
    for p in parts:
        for n, v in p.items():
            by_span[n] = by_span.get(n, 0.0) + v
    longest = sorted(range(len(idle)),
                     key=lambda i: idle[i][0] - idle[i][1])[:traces.TOP]
    return {
        "window_s": hi - lo,
        "idle_s": sum(b - a for a, b in idle),
        "idle_s_by_span": dict(sorted(by_span.items(),
                                      key=lambda kv: -kv[1])),
        "idle_gaps": [[max(parts[i].items(), key=lambda kv: kv[1])[0],
                       idle[i][1] - idle[i][0]] for i in longest],
    }


def main() -> int:
    path = sys.argv[1]
    if not path.endswith(".xplane.pb"):
        path = traces.find_xplane(path)
    print(json.dumps(split(traces.load(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
