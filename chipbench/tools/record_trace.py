"""Record the small trace that ``chipbench/tests`` reduces.

    python3 chipbench/tools/record_trace.py <out_dir>

On the chip: a ``chipbench.window`` span holding three request-like
spans, each running one radar kernel at a small shape (and a host sleep
between them, so the device has idle gaps with known causes).  Writes
the profiler's ``.xplane.pb`` under ``<out_dir>`` and prints, per plane
and line, the event count and the first events, and the reduction.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from chipbench import traces  # noqa: E402
from repro.kernels import ops  # noqa: E402


def main() -> int:
    out = Path(sys.argv[1])
    rng = np.random.default_rng(0)
    field = rng.normal(20, 10, (8, 64, 256)).astype(np.float32)
    flat = field.reshape(8, -1)
    idx = rng.integers(0, flat.shape[1], (1024, 1)).astype(np.int32)
    w = np.ones((1024, 1), np.float32)
    dt = np.full(8, 270.0, np.float32)
    calls = [("qvp_reduce", lambda: ops.qvp_reduce(field, None)),
             ("zr_accum", lambda: ops.zr_accum(field, dt)),
             ("grid_map", lambda: ops.grid_map(flat, idx, w))]
    for _name, fn in calls:                       # compile outside the trace
        np.asarray(fn())
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with TraceAnnotation("chipbench.window"):
        time.sleep(0.02)
        for name, fn in calls:
            with TraceAnnotation("chipbench.product"):
                with TraceAnnotation(f"chipbench.kernel.{name}"):
                    np.asarray(fn())
                time.sleep(0.01)
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = traces.find_xplane(str(out))
    pd = traces.load(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines))
        for line in lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns)
    s = traces.summarize(pd)
    print("SUMMARY", s)
    print("XPLANE", path, Path(path).stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
