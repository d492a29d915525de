"""The traffic generator gives every seed the same work in another
order, and the warm set covers every program and table of a mix."""

from __future__ import annotations

import collections
import json
from urllib.parse import parse_qs, urlsplit

import pytest

from chipbench import traffic

from .conftest import REPO


def _mix(name):
    return json.loads((REPO / "chipbench" / "traffic" / f"{name}.json")
                      .read_text())


def _params(reqs):
    out = collections.Counter()
    for r in reqs:
        for k, v in parse_qs(urlsplit(r["path"]).query).items():
            if k not in ("i0", "i1"):
                out[(r["cls"], k, v[0])] += 1
    return out


ARCHIVES = {"timeseries": {"n_scans": 360}}


@pytest.mark.parametrize("name", sorted(ARCHIVES))
def test_same_multiset_for_every_seed(name):
    mix, arch = _mix(name), ARCHIVES[name]
    a = traffic.schedule(mix, arch, 1, 45.0)
    b = traffic.schedule(mix, arch, 2**31 + 5, 45.0)
    assert len(a) == round(mix["arrival"]["rate_per_s"] * 45.0)
    # the same work: the very same requests (so as many repeated keys),
    # classes, window lengths and tenants; only their order changes
    for key in ("cls", "length", "tenant", "path"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert _params(a) == _params(b)
    assert [r["path"] for r in a] != [r["path"] for r in b]
    gaps_a = sorted(round(y["due"] - x["due"], 9) for x, y in zip(a, a[1:]))
    gaps_b = sorted(round(y["due"] - x["due"], 9) for x, y in zip(b, b[1:]))
    assert len(gaps_a) == len(gaps_b)
    assert all(0.0 <= r["due"] < 45.0 for r in a)
    counts = collections.Counter(r["cls"] for r in a)
    for c in mix["classes"]:
        assert abs(counts[c["name"]] - c["weight"] * len(a)) < 1


@pytest.mark.parametrize("name", sorted(ARCHIVES))
def test_every_kind_is_spread_over_the_run(name):
    """No seed piles one kind of request (class and window length) into
    one part of the run: each quarter of the arrivals holds a quarter of
    each kind, give or take two."""
    mix, arch = _mix(name), ARCHIVES[name]
    for seed in range(2**31, 2**31 + 50):
        reqs = traffic.schedule(mix, arch, seed, 51.0)
        total = collections.Counter((r["cls"], r["length"]) for r in reqs)
        n = len(reqs)
        for q in range(4):
            part = collections.Counter(
                (r["cls"], r["length"]) for r in reqs[q * n // 4:
                                                      (q + 1) * n // 4])
            for kind, c in total.items():
                assert abs(part[kind] - c / 4) <= 2, (seed, kind, q)


def test_day_windows_are_distinct_requests():
    """Every 24 h request in a run is its own product-cache key."""
    reqs = traffic.schedule(_mix("timeseries"), ARCHIVES["timeseries"], 7,
                            45.0)
    day = [r["path"] for r in reqs if r["length"] == 320]
    assert day and len(set(day)) == len(day)


@pytest.mark.parametrize("name", sorted(ARCHIVES))
def test_sample_holds_each_class_longest(name):
    reqs = traffic.schedule(_mix(name), ARCHIVES[name], 3, 45.0)
    keep = traffic.sample(reqs, 12, 3)
    longest = {}
    for r in reqs:
        longest[r["cls"]] = max(longest.get(r["cls"], 0), r["length"])
    for cls, length in longest.items():
        assert any(reqs[i]["cls"] == cls and reqs[i]["length"] == length
                   for i in keep)


def test_warm_set_covers_every_choice_and_length():
    mix = {"classes": [
        {"name": "qpe", "route": "/products/qpe", "weight": 1.0,
         "params": {"repo": "KVNX", "a": {"choice": [200.0, 300.0]},
                    "b": {"choice": [1.4, 1.6, 2.0]}},
         "window": {"lengths": [1, 6], "weights": [0.7, 0.3]}}]}
    warm = traffic.warm_set(mix)
    paths = [w["path"] for w in warm]
    for a in ("200.0", "300.0"):
        for b in ("1.4", "1.6", "2.0"):
            assert any(f"a={a}" in p and f"b={b}" in p for p in paths)
    assert {w["length"] for w in warm} == {1, 6}
    assert len(warm) == 6 + 1


def test_only_the_stratified_process_is_known():
    mix = dict(_mix("timeseries"), arrival={"process": "poisson",
                                            "rate_per_s": 1.0})
    with pytest.raises(ValueError, match="stratified"):
        traffic.schedule(mix, ARCHIVES["timeseries"], 1, 10.0)
