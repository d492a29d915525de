"""The kernels' operation and byte counts against hand counts."""

from __future__ import annotations

import importlib.util

import pytest

from .conftest import REPO


def _cost(kernel):
    path = REPO / "chipbench" / "cost" / f"{kernel}.py"
    spec = importlib.util.spec_from_file_location(f"cost_{kernel}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kernel,shapes,kwargs,ops,nbytes", [
    # 2 scans x 3 azimuths x 4 gates: 24 gates, 8 outputs.  Without a
    # quality moment the field is read once: (24 + 8) * 4 bytes.
    ("qvp_reduce", [(2, 3, 4), (2, 3, 4)], {"quality_min": float("-inf")},
     2 * 24 + 2 * 8, 4 * (24 + 8)),
    # with a quality moment both inputs are read: (48 + 8) * 4
    ("qvp_reduce", [(2, 3, 4), (2, 3, 4)], {"quality_min": 0.85},
     2 * 24 + 2 * 8, 4 * (48 + 8)),
    # 24 gates x 12 operations; 24 gates + 2 weights + 12 outputs
    ("zr_accum", [(2, 3, 4), (2,)], {"a": 200.0, "b": 1.6},
     12 * 24, 4 * (24 + 2 + 12)),
    # the QVP's 19.5 deg cut: 3 scans x 360 azimuths x 5 gates
    ("qvp_reduce", [(3, 360, 5), (3, 360, 5)],
     {"quality_min": float("-inf")},
     2 * 5400 + 2 * 15, 4 * (5400 + 15)),
])
def test_hand_counts(kernel, shapes, kwargs, ops, nbytes):
    assert _cost(kernel).cost(shapes, kwargs) == (ops, nbytes)


@pytest.mark.parametrize("kernel", ["qvp_reduce", "zr_accum"])
def test_program_names(kernel):
    """The trace names a jitted program after its function."""
    from repro.kernels import ops

    assert _cost(kernel).PROGRAM == getattr(ops, f"{kernel}_pallas").__name__
