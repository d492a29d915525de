"""The readers of the program's spans and counters: each value on a
built ``Context``, None where there is nothing to read, and the table
the readers fall back to when the harness hands them none."""

from __future__ import annotations

import sys

import pytest

from .conftest import REPO

SPANS = {
    "http.queue": {"n": 4, "s": 0.002, "bytes": 0},
    "store.get": {"n": 20, "s": 0.5, "bytes": 10_000},
    "store.decode": {"n": 20, "s": 2.0, "bytes": 40_000},
    "store.assemble": {"n": 24, "s": 1.0, "bytes": 40_000},
    "dispatch.call": {"n": 4, "s": 0.25, "bytes": 40_000},
    "dispatch.wait": {"n": 4, "s": 0.05, "bytes": 0},
}
# computed products: 4; (metric, value)
EXPECTED = [
    ("front.queue_ms_per_request", 0.5),
    ("store.get_ms_per_product", 125.0),
    ("store.decode_ms_per_product", 500.0),
    ("store.assemble_ms_per_product", 250.0),
    ("dispatch.call_ms_per_product", 62.5),
    ("dispatch.wait_ms_per_product", 12.5),
]


def _read(metric, ctx):
    from chipbench import run

    mod = run._load_module(REPO / "chipbench" / "metrics" / f"{metric}.py",
                           "chipbench_test_" + metric.replace(".", "_"))
    return mod.read(ctx)


def _ctx(spans, computed=4):
    from chipbench import run

    return run.Context(spans=spans, computed=computed)


@pytest.mark.parametrize("metric,value", EXPECTED)
def test_reader_values(metric, value):
    assert _read(metric, _ctx(SPANS)) == pytest.approx(value)


@pytest.mark.parametrize("metric", [m for m, _v in EXPECTED])
def test_reader_is_none_for_a_program_without_spans(metric):
    assert _read(metric, _ctx({})) is None


@pytest.mark.parametrize("metric", [m for m, _v in EXPECTED
                                    if m.endswith("_per_product")])
def test_reader_is_none_without_computed_products(metric):
    assert _read(metric, _ctx(SPANS, computed=0)) is None


def test_queue_reader_is_none_without_requests():
    spans = dict(SPANS, **{"http.queue": {"n": 0, "s": 0.0, "bytes": 0}})
    assert _read("front.queue_ms_per_request", _ctx(spans)) is None


def test_readers_fall_back_to_the_traced_part_of_the_table(tmp_path):
    """A context without ``spans`` reads ``repro.obs`` itself: what it
    recorded while a profiler session ran, and nothing outside one."""
    import jax
    from chipbench import run
    from repro import obs

    def decode_ms():
        return _read("store.decode_ms_per_product",
                     run.Context(computed=1))

    with obs.span("store.decode"):
        pass
    before = decode_ms()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("store.decode"):
            pass
    finally:
        jax.profiler.stop_trace()
    with obs.span("store.decode"):
        pass
    row = obs.snapshot(traced=True)["store.decode"]
    assert decode_ms() == pytest.approx(1e3 * row["s"])
    assert decode_ms() > (before or 0.0)


def test_readers_are_none_for_a_program_without_obs(monkeypatch):
    import repro
    from chipbench import run

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    for metric, _v in EXPECTED:
        assert _read(metric, run.Context(computed=4)) is None
