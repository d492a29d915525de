"""The trace reduction: interval union, idle gaps, attribution, and a
small trace recorded on a TPU v5e (``data/``, made by
``tools/record_trace.py``)."""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import traces

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_touching():
    assert traces.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 6)]) == \
        [(0, 2.5), (3, 4), (5, 6)]


def test_gaps_are_the_complement_inside_the_window():
    busy = traces.union([(1, 2), (4, 5)])
    assert traces.gaps(busy, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert traces.gaps(busy, 1.5, 4.5) == [(2, 4)]
    assert traces.gaps([], 0, 1) == [(0, 1)]


class _Ev:
    def __init__(self, name, start_s, dur_s):
        self.name, self.start_ns, self.duration_ns = \
            name, start_s * 1e9, dur_s * 1e9


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Space:
    def __init__(self, planes):
        self.planes = planes


def test_summary_on_a_built_trace():
    host = _Plane("/host:CPU", [
        _Line("main", [_Ev("chipbench.window", 0.0, 10.0)]),
        _Line("worker", [_Ev("chipbench.product", 1.0, 5.0),
                         _Ev("chipbench.compute_product", 1.5, 4.0),
                         _Ev("chipbench.kernel.zr_accum", 4.0, 1.0)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_zr_accum_pallas(7)", 4.2, 0.5),
                              _Ev("jit_grid_map_pallas(9)", 9.5, 1.0)]),
        _Line("XLA Ops", [_Ev("zr", 4.2, 0.5), _Ev("gather", 9.5, 0.3),
                          _Ev("combine", 9.7, 0.8)])])
    s = traces.summarize(_Space([host, dev]))
    assert s.window_s == pytest.approx(10.0)
    # busy: [4.2, 4.7] and [9.5, 10.0] clipped to the window
    assert s.busy_s[0] == pytest.approx(1.0)
    assert s.program_seconds("zr_accum_pallas") == pytest.approx(0.5)
    assert s.program_seconds("grid_map_pallas") == pytest.approx(0.5)
    assert s.program_seconds("qvp_reduce_pallas") is None
    gaps = dict((round(d, 6), n) for n, d in s.idle_gaps)
    assert gaps[4.2] == "chipbench.compute_product"     # [0, 4.2): mid 2.1
    assert gaps[4.8] == "idle: no request in service"   # [4.7, 9.5): mid 7.1
    assert s.top_ops[0][0] == "zr"


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
def test_recorded_v5e_trace():
    s = traces.summarize(traces.load(str(RECORDED[0])))
    assert 0.05 < s.window_s < 5.0
    assert len(s.busy_s) == 1 and 0.0 < s.busy_s[0] < s.window_s
    for kernel in ("qvp_reduce_pallas", "zr_accum_pallas", "grid_map_pallas"):
        assert s.program_seconds(kernel) > 0.0
    # the host sleeps between the kernels are the longest idle gaps, each
    # named by the span open around it
    names = {n for n, _d in s.idle_gaps}
    assert names <= {"chipbench.product", "chipbench.kernel.qvp_reduce",
                     "chipbench.kernel.zr_accum", "chipbench.kernel.grid_map",
                     "idle: no request in service"}
    assert "chipbench.product" in names
