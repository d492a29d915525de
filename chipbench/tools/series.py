"""Run ``chipbench/run.py`` several times in a row, one process each.

    python3 chipbench/tools/series.py --out chiprun_out/<tag> \
        <workload>:<seed>:<seconds>:<trace> ...

Each run's standard output and error go to ``<out>/<n>.out|err``; its
exit code, wall seconds and the result line's metrics, checks and
``correct`` are printed as one JSON line, and ``<out>/summary.jsonl``
collects them.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("runs", nargs="+")
    args = p.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for n, spec in enumerate(args.runs):
        workload, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, "chipbench/run.py", "--workload", workload,
               "--seed", seed, "--seconds", seconds, "--trace", trace]
        t0 = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True)
        wall = time.monotonic() - t0
        (out / f"{n}.out").write_bytes(proc.stdout)
        (out / f"{n}.err").write_bytes(proc.stderr)
        lines = proc.stdout.decode(errors="replace").strip().splitlines()
        doc = {"n": n, "run": spec, "rc": proc.returncode, "wall_s": wall}
        try:
            last = json.loads(lines[-1])
            doc.update({k: last.get(k) for k in
                        ("correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks")})
            doc["earlier"] = [json.loads(x) for x in lines[:-1]
                              if x.startswith('{"phase"')]
        except (IndexError, ValueError):
            doc["stderr_tail"] = proc.stderr.decode(errors="replace")[-3000:]
        line = json.dumps(doc)
        print(line, flush=True)
        with open(out / "summary.jsonl", "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
