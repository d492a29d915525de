"""The idle-time split by host span (``tools/idle_by_span.py``) on
built traces and on the recorded v5e trace."""

from __future__ import annotations

import pytest

from chipbench import traces
from chipbench.tools import idle_by_span

from .test_traces import RECORDED, _Ev, _Line, _Plane, _Space


def test_program_spans_split_idle_time_across_threads():
    """Two workers in ``repro.*`` spans (with the harness's around them):
    each idle instant goes to the innermost program span of each thread
    inside one, shared equally, and a gap is named by its largest part."""
    host = _Plane("/host:CPU", [
        _Line("main", [_Ev("chipbench.window", 0.0, 10.0)]),
        _Line("worker-1", [
            _Ev("repro.http.request", 0.0, 6.0),
            _Ev("chipbench.compute_product", 0.5, 5.0),
            _Ev("repro.product.compute", 0.5, 5.0),
            _Ev("repro.store.decode", 1.0, 2.0),
            _Ev("repro.store.assemble", 3.0, 1.0),
            _Ev("repro.dispatch.call", 4.0, 0.5),
            _Ev("chipbench.kernel.zr_accum", 4.1, 0.3)]),
        _Line("worker-2", [
            _Ev("repro.http.request", 2.0, 2.0),
            _Ev("repro.store.get", 2.0, 1.0)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_zr_accum_pallas(7)", 4.5, 0.5)]),
        _Line("XLA Ops", [_Ev("zr", 4.5, 0.5)])])
    out = idle_by_span.split(_Space([host, dev]))
    by = out["idle_s_by_span"]
    # [0, 4.5) and [5, 10) idle; the parts add up to the idle seconds
    assert out["idle_s"] == pytest.approx(9.5)
    assert sum(by.values()) == pytest.approx(9.5)
    assert by["repro.store.decode"] == pytest.approx(1.0 + 0.5)  # 1-2, 2-3
    assert by["repro.store.get"] == pytest.approx(0.5)            # 2-3
    assert by["repro.store.assemble"] == pytest.approx(0.5)       # 3-4
    assert by["repro.http.request"] == pytest.approx(0.5 + 0.5 + 0.5)
    assert by["repro.dispatch.call"] == pytest.approx(0.5)        # 4-4.5
    assert by["repro.product.compute"] == pytest.approx(0.5 + 0.5)
    assert by[traces.NO_SPAN] == pytest.approx(4.0)               # 6-10
    # no harness span is used once the program has its own
    assert not any(n.startswith("chipbench.") for n in by)
    gaps = dict((round(d, 6), n) for n, d in out["idle_gaps"])
    assert gaps[4.5] == "repro.store.decode"
    assert gaps[5.0] == traces.NO_SPAN


def test_harness_spans_name_the_gaps_as_the_summary_does():
    """Without program spans the harness's are used, and on the summary's
    own built trace the two namings agree."""
    host = _Plane("/host:CPU", [
        _Line("main", [_Ev("chipbench.window", 0.0, 10.0)]),
        _Line("worker", [_Ev("chipbench.product", 1.0, 5.0),
                         _Ev("chipbench.compute_product", 1.5, 4.0),
                         _Ev("chipbench.kernel.zr_accum", 4.0, 1.0)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Ops", [_Ev("zr", 4.2, 0.5), _Ev("gather", 9.5, 0.3),
                          _Ev("combine", 9.7, 0.8)])])
    space = _Space([host, dev])
    out = idle_by_span.split(space)
    assert sorted(map(tuple, out["idle_gaps"])) == \
        sorted(traces.summarize(space).idle_gaps)
    assert out["idle_s"] == pytest.approx(9.0)


def test_innermost_names_each_instant_by_the_deepest_span():
    pieces = idle_by_span.innermost([(0, 10, "a"), (2, 5, "b"),
                                     (3, 4, "c"), (6, 7, "d")])
    assert pieces == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"),
                      (5, 6, "a"), (6, 7, "d"), (7, 10, "a")]
    assert idle_by_span.innermost([]) == []


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
def test_recorded_v5e_trace_splits_all_its_idle_time():
    space = traces.load(str(RECORDED[0]))
    out = idle_by_span.split(space)
    s = traces.summarize(space)
    assert out["window_s"] == pytest.approx(s.window_s)
    assert out["idle_s"] == pytest.approx(s.window_s - s.busy_s[0])
    assert sum(out["idle_s_by_span"].values()) == pytest.approx(
        out["idle_s"])
    assert "chipbench.product" in out["idle_s_by_span"]
