"""Read the compared numbers of sound runs and of the control.

    python3 chipbench/tools/readings.py --workload <cell> \
        --seconds <s> --seeds 1,2,3 [--control-seeds 1,2,3]

Per seed, in one process: the archive, the warm-up and one open-loop
window at the cell's own rate, as a run makes them; then the numbers
the comparison takes over the run's sample (the program's readings),
and for the control seeds the same numbers with the reference in
bfloat16 put in the program's place (the control's readings).  One JSON
line per seed; the limits in ``limits.json`` are set from these.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import run  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    args = p.parse_args()
    cell = run.load_cell(ROOT, args.workload)
    run.init(cell)
    from chipbench import archive, reference, traffic

    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        workdir = tempfile.mkdtemp(prefix="chipbench-readings-")
        try:
            data = archive.generate(cell.cfg, seed)
            catalog = archive.build(cell.cfg, data, workdir)
            run.warm(catalog, cell)
            reqs = traffic.schedule(cell.mix, cell.archive, seed,
                                    args.seconds)
            keep = traffic.sample(reqs, int(cell.mix["compare"]), seed)
            served = run.Served(catalog, cell.mix["tenants"])
            try:
                win = run.drive(served, reqs, keep, args.seconds)
            finally:
                served.close()
            verdict = run.check(data, reqs, keep, win, cell.limits)
            doc = {"seed": seed, "compared": verdict.compared,
                   "program": verdict.numbers, "correct": verdict.correct}
            if seed in control:
                sample = [i for i in keep if win.records[i]["status"] == 200]
                doc["control"] = reference.check_sample(
                    reference.Reference(data),
                    [reqs[i]["path"] for i in sample], [None] * len(sample),
                    precision="bfloat16", workers=1)
            run.emit(doc)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
