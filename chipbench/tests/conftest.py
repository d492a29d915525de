"""Fixtures for the benchmark's own tests (``pytest chipbench/tests``).

``toy_root`` is a checkout-like directory holding a copy of the
benchmark's data files plus a toy cell, added the way a later change
adds one: a configuration file, a traffic file and a ``workloads``
entry, and nothing else.  The toy cell keeps the real cell's shapes
(cuts of their own geometry, products, windows, tenants) at 36 and 18
azimuths x 40 gates.
``cpu_run`` runs a cell there on the CPU with the Pallas kernels in
interpret mode, skipping only the harness's look for a chip.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

TOY_CELLS = {
    "toy.timeseries": ("kvnx-vcp212-day", "toy-day", "timeseries", "toy-ts"),
}
TOY_CUTS = {"0.5": {"n_azimuth": 36, "n_gates": 40, "gate_m": 1000.0,
                    "first_gate_m": 500.0},
            "19.5": {"n_azimuth": 18, "n_gates": 40, "gate_m": 1000.0,
                     "first_gate_m": 500.0}}


def make_toy_root(root: Path, rate: float = 4.0) -> Path:
    bench = root / "chipbench"
    bench.mkdir(parents=True)
    for sub in ("metrics", "cost", "configs", "traffic"):
        shutil.copytree(REPO / "chipbench" / sub, bench / sub)
    for name in ("limits.json", "peaks.json"):
        shutil.copy(REPO / "chipbench" / name, bench / name)
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1e10}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    for cell, (cfg_name, toy_cfg, mix_name, toy_mix) in TOY_CELLS.items():
        cfg = json.loads((bench / "configs" / f"{cfg_name}.json").read_text())
        cfg.update(name=toy_cfg, time_chunk=4, n_scans=24, cuts=TOY_CUTS)
        (bench / "configs" / f"{toy_cfg}.json").write_text(json.dumps(cfg))
        mix = json.loads((bench / "traffic" / f"{mix_name}.json").read_text())
        mix["arrival"]["rate_per_s"] = rate
        for c in mix["classes"]:
            c["window"]["lengths"] = [3, 6, 20]
        (bench / "traffic" / f"{toy_mix}.json").write_text(json.dumps(mix))
        doc["configs"].append({"name": toy_cfg, "source": "toy",
                               "file": f"chipbench/configs/{toy_cfg}.json",
                               "reduced": [], "why": "toy"})
        doc["workloads"].append({"name": cell, "config": toy_cfg,
                                 "traffic": toy_mix, "chips": 1,
                                 "why": "toy"})
        for m in doc["per_layer"]:
            m["workloads"] = m["workloads"] + [cell]
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return root


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def cpu_run(monkeypatch, toy_root, capsys):
    """``cpu_run(cell, seed, seconds)`` -> (exit code, last stdout line
    as a dict or None, stderr)."""
    from chipbench import run
    from repro.kernels import ops

    monkeypatch.setattr(run, "require_chip", lambda chips: None)
    monkeypatch.setattr(ops, "_resolve", lambda mode: (True, True))

    def go(cell, seed, seconds=3.0):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      root=toy_root)
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), err

    return go
