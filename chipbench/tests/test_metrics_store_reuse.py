"""The reader of the store's output arena (``store.read.reused``,
``store.read.fresh``): its value on a built ``Context``, and None where
the program records neither counter, as a program without the arena
does."""

from __future__ import annotations

import sys

import pytest

from .test_metrics import _ctx, _read

METRIC = "store.read_reuse_share"
SPANS = {
    "store.read": {"n": 8, "s": 1.6, "bytes": 40_000},
    "store.read.reused": {"n": 6, "s": 0.01, "bytes": 27_000},
    "store.read.fresh": {"n": 2, "s": 0.01, "bytes": 3_000},
}


def test_reader_value_is_a_share_of_bytes():
    assert _read(METRIC, _ctx(SPANS)) == pytest.approx(90.0)


@pytest.mark.parametrize("spans", [{}, {"store.read": SPANS["store.read"]}],
                         ids=["no_spans", "reads_below_the_threshold"])
def test_reader_is_none_without_either_counter(spans):
    assert _read(METRIC, _ctx(spans)) is None


@pytest.mark.parametrize("present,share", [
    (("store.read.fresh",), 0.0),
    (("store.read.reused",), 100.0),
])
def test_share_of_outputs_of_one_kind(present, share):
    """Every output mapped fresh reads 0 %; every one in a kept block,
    100 %."""
    spans = {k: v for k, v in SPANS.items()
             if k == "store.read" or k in present}
    assert _read(METRIC, _ctx(spans)) == share


def test_reader_is_none_for_a_program_without_obs(monkeypatch):
    import repro
    from chipbench import run

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    assert _read(METRIC, run.Context(computed=4)) is None
