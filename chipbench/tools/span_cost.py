"""What one of the program's spans costs on this host.

    python3 chipbench/tools/span_cost.py [<trace_dir>]

Times empty ``repro.obs.span`` blocks (with no arguments, and with one
as the kernel dispatch spans carry) and, for comparison, an empty
``jax.profiler.TraceAnnotation``: first with no profiler session, then
with one running at the harness's options (host tracer level 1, no
Python tracer), writing under ``<trace_dir>`` (a temporary directory by
default).  Prints one JSON line of microseconds per span, each the
median of 5 rounds of 100,000.
"""

from __future__ import annotations

import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro import obs  # noqa: E402

N, ROUNDS = 100_000, 5


def _us(block) -> float:
    times = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(N):
            block()
        times.append((time.perf_counter() - t0) / N * 1e6)
    return statistics.median(times)


def _bare() -> None:
    with TraceAnnotation("repro.cost"):
        pass


def _span() -> None:
    with obs.span("cost"):
        pass


def _span_args() -> None:
    with obs.span("cost", nbytes=8, kernel="zr_accum"):
        pass


def _all():
    return {"annotation_us": _us(_bare), "span_us": _us(_span),
            "span_args_us": _us(_span_args)}


def main() -> int:
    out = {"backend": jax.default_backend(), "off": _all()}
    trace_dir = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        out["on"] = _all()
    finally:
        jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
