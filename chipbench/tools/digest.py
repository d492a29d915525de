"""Digest of a ``prove.sh`` output directory.

    python3 chipbench/tools/digest.py <out_dir>

Prints the knee, the sweep (rate, completed/s, p50, p90, failures,
medians of the earlier and later half of arrivals), the readings (each
compared number's largest over the program's seeds and smallest over the
control's), and per set of runs each end-to-end metric's values, median
and spread (the distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` over the median), with ``correct``
and the set-up's compile counts; then the traced runs' per-layer metrics.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path


def _lines(path: Path):
    if not path.exists():
        return []
    return [json.loads(x) for x in path.read_text().splitlines()
            if x.startswith("{")]


def spread(values):
    q1, _med, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else 0.0


def _setup(run):
    for e in run.get("earlier") or []:
        if e.get("phase") == "window":
            return e.get("setup", {})
    return {}


def main() -> int:
    out = Path(sys.argv[1])
    for line in _lines(out / "knee.json"):
        print("knee", json.dumps(line))
    for s in _lines(out / "sweep.jsonl"):
        print("sweep rate=%s done/s=%.3f p50=%.3f p90=%.3f failed=%d "
              "halves=%.3f/%.3f late_max=%.3f" % (
                  s["rate"], s["products_per_s"], s["latency_p50_s"],
                  s["latency_p90_s"], s["failed"], s["p50_first_half_s"],
                  s["p50_second_half_s"], s["late_max_s"]))
    readings = _lines(out / "readings.jsonl")
    if readings:
        prog, ctrl = {}, {}
        for r in readings:
            for k, v in r["program"].items():
                prog.setdefault(k, []).append(v)
            for k, v in (r.get("control") or {}).items():
                ctrl.setdefault(k, []).append(v)
        print("readings seeds=%d correct=%s" % (
            len(readings), [r["correct"] for r in readings]))
        for k in sorted(prog):
            print("  %s program max=%r control min=%r" % (
                k, max(prog[k]), min(ctrl[k]) if k in ctrl else None))
    for name in ("setA", "setB", "traced"):
        runs = _lines(out / name / "summary.jsonl")
        if not runs:
            continue
        print(f"{name}: correct={[r.get('correct') for r in runs]} "
              f"rc={[r['rc'] for r in runs]} "
              f"wall={[round(r['wall_s'], 1) for r in runs]}")
        print("  compile(setup)=%s" % [
            {k: v for k, v in _setup(r).get("compile", {}).items()
             if k != "compile_s"} for r in runs])
        metrics = sorted({k for r in runs for k in (r.get("metrics") or {})})
        for m in metrics:
            vals = [r["metrics"][m]["value"] for r in runs
                    if m in (r.get("metrics") or {})]
            extra = (" median=%.6g spread=%.4f" % (statistics.median(vals),
                                                  spread(vals))
                     if len(vals) >= 2 else "")
            print("  %s %s%s" % (m, [round(v, 5) for v in vals], extra))
        peaks = [r["device"]["memory_peak_bytes"] for r in runs
                 if r.get("device")]
        print("  memory_peak_bytes", peaks)
        if name == "traced":
            for r in runs:
                device = r.get("device") or {}
                print("  busy/window", device.get("busy_s"),
                      device.get("window_s"))
                print("  breakdown", json.dumps(r.get("breakdown"))[:1500])
    return 0


if __name__ == "__main__":
    sys.exit(main())
