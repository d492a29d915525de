"""The readers of the store read path's span and counters
(``store.read``, ``store.read.pooled``, ``store.read.inline``): each
value on a built ``Context``, and None where the program records none
of them, as a program without a shared read pool does."""

from __future__ import annotations

import sys

import pytest

from .test_metrics import _ctx, _read

SPANS = {
    "store.read": {"n": 8, "s": 1.6, "bytes": 40_000},
    "store.read.pooled": {"n": 18, "s": 2.4, "bytes": 0},
    "store.read.inline": {"n": 6, "s": 0.8, "bytes": 0},
}
# computed products: 4; (metric, value)
EXPECTED = [
    ("store.read_ms_per_product", 400.0),
    ("store.read_pooled_share", 75.0),
]


@pytest.mark.parametrize("metric,value", EXPECTED)
def test_reader_values(metric, value):
    assert _read(metric, _ctx(SPANS)) == pytest.approx(value)


@pytest.mark.parametrize("metric", [m for m, _v in EXPECTED])
def test_reader_is_none_for_a_program_without_spans(metric):
    assert _read(metric, _ctx({})) is None


def test_read_ms_is_none_without_computed_products():
    assert _read("store.read_ms_per_product",
                 _ctx(SPANS, computed=0)) is None


@pytest.mark.parametrize("present,share", [
    (("store.read.inline",), 0.0),
    (("store.read.pooled",), 100.0),
])
def test_pooled_share_of_reads_of_one_kind(present, share):
    """A program that reads serially counts only inline chunks (0 %);
    one whose helpers took every chunk, only pooled ones (100 %)."""
    spans = {k: v for k, v in SPANS.items()
             if not k.startswith("store.read.") or k in present}
    assert _read("store.read_pooled_share", _ctx(spans)) == share


def test_readers_are_none_for_a_program_without_obs(monkeypatch):
    import repro
    from chipbench import run

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    for metric, _v in EXPECTED:
        assert _read(metric, run.Context(computed=4)) is None
