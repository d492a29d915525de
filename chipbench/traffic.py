"""The one traffic generator: a traffic mix file -> an open-loop schedule.

A mix (``chipbench/traffic/<name>.json``) names an arrival process and
rate, tenants and request classes.  Each class has a route, parameters
and a window of scan indices (``i0``/``i1``)::

    {"name": "qpe", "weight": 0.5, "route": "/products/qpe",
     "params": {"repo": "KVNX", "sweep": 0,
                "a": {"choice": [200.0, 300.0], "weights": [3, 1]}},
     "window": {"lengths": [13, 40], "weights": [0.5, 0.5]}}

A parameter is a constant or a ``choice``.

The one arrival process is ``stratified``: deterministic in its work and
its gaps, random only in their order.  Every seed gets the same multiset
of requests: the request count is ``rate * seconds``; class, choice and
window-length counts are the weights' exact shares (largest remainder);
window starts are evenly spread over the archive; and the gaps between
arrivals are the exponential distribution's quantiles, so their mean and
spread are a Poisson process's.  The requests themselves are drawn from a
fixed stream; the seed permutes the gaps and draws a stratified order:
each kind of request (class and window length) is spread evenly over the
run, its j-th request at a place drawn in the j-th of as many equal
strata.  So seeds change the order and the data but not the work, and no
seed piles the heaviest kind into one burst.  Bursts of heavy requests,
which Poisson arrivals would bring, are not measured by such a mix.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple
from urllib.parse import urlencode

import numpy as np


def _counts(weights, n: int) -> List[int]:
    """``n`` split by ``weights`` into whole counts (largest remainder)."""
    w = np.asarray(weights, dtype=np.float64)
    share = w / w.sum() * n
    out = np.floor(share).astype(int)
    order = np.argsort(-(share - out), kind="stable")
    out[order[: n - int(out.sum())]] += 1
    return out.tolist()


def _spread(n: int, weights, rng) -> List[int]:
    """Exact-count assignment of ``len(weights)`` options to ``n`` slots,
    in an order drawn from ``rng``."""
    idx = np.repeat(np.arange(len(weights)), _counts(weights, n))
    return rng.permutation(idx).tolist()


def _choices(params: Dict[str, Any]) -> List[Tuple[str, list, list]]:
    return [(k, v["choice"], v.get("weights", [1] * len(v["choice"])))
            for k, v in params.items() if isinstance(v, dict) and "choice" in v]


def _apply(params: Dict[str, Any], picks: Dict[str, Any], i0: int,
           length: int) -> Dict[str, Any]:
    out = {k: v for k, v in params.items()
           if not (isinstance(v, dict) and "choice" in v)}
    out.update(picks, i0=i0, i1=i0 + length)
    return out


def _path(route: str, params: Dict[str, Any]) -> str:
    return f"{route}?{urlencode(sorted(params.items()))}"


def schedule(mix: Dict[str, Any], archive: Dict[str, Any], seed: int,
             seconds: float) -> List[Dict[str, Any]]:
    """The requests due in ``[0, seconds)``: dicts with ``due`` (s from
    the window's start), ``path``, ``tenant``, ``cls`` and ``length``.

    ``archive`` gives ``n_scans``."""
    process = mix["arrival"]["process"]
    if process != "stratified":
        raise ValueError(f"arrival process {process!r}: the generator "
                         "knows only 'stratified'")
    fixed = np.random.default_rng(0)
    rate = float(mix["arrival"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    classes = mix["classes"]
    per_class = _counts([c["weight"] for c in classes], n)
    n_scans = int(archive["n_scans"])

    reqs: List[Dict[str, Any]] = []
    for cls, count in zip(classes, per_class):
        if count == 0:
            continue
        win = cls["window"]
        lengths = [int(x) for x in win["lengths"]]
        len_idx = _spread(count, win["weights"], fixed)
        picks = [dict() for _ in range(count)]
        for name, values, weights in _choices(cls["params"]):
            for slot, vi in zip(picks, _spread(count, weights, fixed)):
                slot[name] = values[vi]
        # window starts: evenly spread over the positions each length has
        starts: Dict[int, List[int]] = {}
        for li, length in enumerate(lengths):
            m = len_idx.count(li)
            span = n_scans - length + 1
            if span < 1:
                raise ValueError(f"window of {length} scans exceeds the "
                                 f"archive's {n_scans}")
            pos = [int((k + 0.5) * span / m) for k in range(m)]
            starts[length] = fixed.permutation(pos).tolist() if m else []
        for slot, li in zip(picks, len_idx):
            length = lengths[li]
            params = _apply(cls["params"], slot, starts[length].pop(), length)
            reqs.append({"cls": cls["name"], "length": length,
                         "path": _path(cls["route"], params)})

    rng = np.random.default_rng(int(seed) % 2**63)
    kinds: Dict[Tuple[str, int], List[int]] = {}
    for i, r in enumerate(reqs):
        kinds.setdefault((r["cls"], r["length"]), []).append(i)
    place = np.empty(len(reqs))
    for members in kinds.values():
        c = len(members)
        place[rng.permutation(members)] = (np.arange(c) + rng.random(c)) / c
    order = np.argsort(place, kind="stable")
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= seconds / float(gaps.sum())
    tenants = mix["tenants"]
    out = []
    for k, i in enumerate(order):
        r = dict(reqs[i], due=float(due[k]), tenant=tenants[k % len(tenants)])
        out.append(r)
    return out


def warm_set(mix: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One request per program and per table the mix can touch: every
    combination of choices at the first window length, and each further
    length once, all at the archive's first scan."""
    out = []
    for cls in mix["classes"]:
        lengths = [int(x) for x in cls["window"]["lengths"]]
        choices = _choices(cls["params"])
        combos = [dict()]
        for name, values, _w in choices:
            combos = [dict(c, **{name: v}) for c in combos for v in values]
        plan = [(c, lengths[0]) for c in combos]
        plan += [(combos[0], length) for length in lengths[1:]]
        for picks, length in plan:
            params = _apply(cls["params"], picks, 0, length)
            out.append({"cls": cls["name"], "length": length,
                        "path": _path(cls["route"], params)})
    return out


def sample(reqs: List[Dict[str, Any]], k: int, seed: int) -> List[int]:
    """Indices of ``k`` requests to compare, drawn from ``seed``, always
    holding each class's longest window."""
    rng = np.random.default_rng((int(seed) + 1) % 2**63)
    must = {}
    for i, r in enumerate(reqs):
        best = must.get(r["cls"])
        if best is None or r["length"] > reqs[best]["length"]:
            must[r["cls"]] = i
    chosen = list(dict.fromkeys(must.values()))
    rest = [i for i in rng.permutation(len(reqs)).tolist() if i not in chosen]
    chosen += rest[: max(0, k - len(chosen))]
    return sorted(chosen)


__all__ = ["schedule", "warm_set", "sample"]
