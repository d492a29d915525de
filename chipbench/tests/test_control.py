"""The comparison that decides ``correct`` fails what it must.

- The control (the reference in bfloat16, the precision below the
  configurations' float32, put in the program's place) fails the limits
  in ``limits.json`` on each cell's sample; the float32 reference passes.
- A run whose timed path is broken underneath (the kernels the product
  path dispatches to) comes out ``correct: false``: once with an answer
  altered where it is produced, once with half of the batch (azimuths
  or scans) left out and the rest averaged or accumulated.
"""

from __future__ import annotations

import json

import pytest

from .conftest import TOY_CELLS


def _control_numbers(toy_root, cell, seed, precision):
    from chipbench import archive, reference, run, traffic

    c = run.load_cell(toy_root, cell)
    data = archive.generate(c.cfg, seed)
    reqs = traffic.schedule(c.mix, c.archive, seed, 3.0)
    keep = traffic.sample(reqs, int(c.mix["compare"]), seed)
    numbers = reference.check_sample(
        reference.Reference(data), [reqs[i]["path"] for i in keep],
        [None] * len(keep), precision=precision)
    numbers["failed"] = 0.0
    return reference.judge(numbers, c.limits)


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_control_fails_and_reference_passes(toy_root, cell):
    for seed in (1, 2, 3):
        ok, table = _control_numbers(toy_root, cell, seed, "bfloat16")
        assert not ok, table
        ok, table = _control_numbers(toy_root, cell, seed, "float32")
        assert ok, table


def _altered(fn):
    def broken(*args, **kwargs):
        return fn(*args, **kwargs) + 0.01
    return broken


def _half(kernel, fn):
    """The kernel over half of its batch, the rest averaged (QVP's
    azimuths) or scaled up (QPE's scans)."""
    if kernel == "qvp_reduce":
        return lambda field, quality, **kw: fn(field[:, ::2], quality[:, ::2],
                                               **kw)
    return lambda dbz, dt, **kw: fn(dbz[::2], dt[::2] * 2.0, **kw)


KERNELS = {"toy.timeseries": ("qvp_reduce", "zr_accum")}


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_broken_path_is_not_correct(cpu_run, monkeypatch, cell, fault):
    from repro.kernels import ops

    for kernel in KERNELS[cell]:
        name = f"{kernel}_pallas"
        fn = getattr(ops, name)
        monkeypatch.setattr(ops, name, _altered(fn) if fault ==
                            "answer_altered" else _half(kernel, fn))
    rc, last, err = cpu_run(cell, seed=5)
    assert rc == 0, err
    assert last["correct"] is False, json.dumps(last["checks"])
