"""Read a knee sweep (``sweep.py``'s lines) and name the cell's rate.

    python3 chipbench/tools/knee.py <sweep.jsonl> [--set <traffic.json>]

A window is sustained when no request failed, the requests completed
inside it keep up with those offered (at least nine tenths), the backlog
does not grow (the later half of the arrivals waits at most half again
as long, by the median, as the earlier half), and the p90 stays within
30 % of the median p90 at the lowest rate swept (a stall that holds
every request back for seconds shows there first).  A rate is sustained
when every seed's window at it is.  The knee is the highest sustained
rate below the first that is not; the cell's rate is four fifths of it,
to one decimal (where every rate was sustained the knee lies above the
sweep, and ``first_not_sustained`` is null).  With ``--set`` the rate is
written into the traffic file's ``arrival.rate_per_s``.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
from collections import defaultdict


def sustained(line, base_p90: float) -> bool:
    return (line["failed"] == 0
            and line["products_per_s"] >= 0.9 * line["rate"]
            and line["p50_second_half_s"] <= 1.5 * line["p50_first_half_s"]
            and line["latency_p90_s"] <= 1.3 * base_p90)


def knee(lines):
    """(knee, the first rate not sustained or None, {rate: sustained})."""
    by_rate = defaultdict(list)
    for line in lines:
        by_rate[line["rate"]].append(line)
    rates = sorted(by_rate)
    base = statistics.median(x["latency_p90_s"] for x in by_rate[rates[0]])
    ok = {r: all(sustained(x, base) for x in by_rate[r]) for r in rates}
    best, over = None, None
    for r in rates:
        if not ok[r]:
            over = r
            break
        best = r
    if best is None:
        raise ValueError("no rate of the sweep was sustained")
    return best, over, ok


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("sweep")
    p.add_argument("--set", dest="traffic")
    args = p.parse_args()
    with open(args.sweep) as f:
        lines = [json.loads(x) for x in f if x.startswith("{")]
    k, over, ok = knee(lines)
    rate = round(0.8 * k, 1)
    print(json.dumps({"knee_per_s": k, "first_not_sustained": over,
                      "rate_per_s": rate,
                      "sustained": {str(r): v for r, v in ok.items()}}))
    if args.traffic:
        with open(args.traffic) as f:
            text = f.read()
        text, n = re.subn(r'("rate_per_s":\s*)[0-9.eE+-]+', rf"\g<1>{rate}",
                          text, count=1)
        if n != 1:
            raise ValueError(f"no rate_per_s in {args.traffic}")
        with open(args.traffic, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
