"""Find a cell's knee: one set-up, then one open-loop window per rate and
seed.

    python3 chipbench/tools/sweep.py --workload <cell> --seeds <a,b> \
        --seconds <s> --rates 2,3,4

The archive is made from the first seed.  Each (rate, seed) gets a
fresh ``ArchiveService`` (cold product cache) on the same archive and
programs, the mix's request set at that rate in the seed's order, and
one JSON line: rate, seed, products/s, p50, p90, failures, generator
lateness, per-class tails and the median latency of the earlier and the
later half of the arrivals.  ``knee.py`` reads the lines.
"""

from __future__ import annotations

import argparse
import copy
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
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = run.load_cell(ROOT, args.workload)
    run.init(cell)
    from chipbench import archive, traffic

    workdir = tempfile.mkdtemp(prefix="chipbench-sweep-")
    try:
        data = archive.generate(cell.cfg, seeds[0])
        catalog = archive.build(cell.cfg, data, workdir)
        run.warm(catalog, cell)
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = copy.deepcopy(cell.mix)
            mix["arrival"]["rate_per_s"] = rate
            for seed in seeds:
                reqs = traffic.schedule(mix, cell.archive, seed,
                                        args.seconds)
                served = run.Served(catalog, mix["tenants"])
                try:
                    win = run.drive(served, reqs, [], args.seconds)
                finally:
                    served.close()
                e2e = run.end_to_end(win, args.seconds)
                e2e.pop("setup_s")
                # a growing backlog: later arrivals wait longer than earlier
                by_due = sorted(win.records, key=lambda r: r["due"])
                half = len(by_due) // 2
                halves = [run.percentile([r["latency_s"] for r in part], 0.5)
                          for part in (by_due[:half], by_due[half:])]
                run.emit(dict(run.window_line(win, args.seconds), rate=rate,
                              seed=seed, p50_first_half_s=halves[0],
                              p50_second_half_s=halves[1], **e2e))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
