#!/usr/bin/env python3
"""Drive the archive product path once on a TPU and check its answers.

    python chip_smoke.py [--seed N]

One process, through the entry points a user calls:

1. archive — ``generate_raw_archive`` + ``ingest`` build two catalogued
   sites (KVNX, KTLX) at full VCP-212 geometry: 14 cuts of 720 azimuths
   x 1192 gates, all seven moments, six storm-mode scans each (27 min).
2. serve — ``ArchiveServer(ArchiveService(catalog))`` answers GETs for
   ``/products/qvp``, ``/qpe``, ``/cappi`` (all 14 cuts),
   ``/column_max`` and ``/mosaic`` (both sites) from a client thread,
   first cold (compiling) and then from a fresh service (compiled); the
   two rounds must return identical bytes.
3. references — every product is checked against a reference that does
   not run on the TPU: the ``*_from_volumes`` numpy baselines for QVP and
   QPE, the ``kernels/ref.py`` oracles on the host CPU for the gridded
   products.  NaN patterns must be identical; values agree within the
   tolerances stated below.
4. streaming — ``etl.LiveFeed`` appends a seventh scan and an incremental
   CAPPI catches up (``grid_map`` + ``grid_update`` on the chip); it must
   equal the from-scratch CAPPI bit for bit.
5. kernels — each of the four radar kernels that the phases above called
   is compiled again from the recorded shapes, and must be a Mosaic
   ``tpu_custom_call`` (no interpret mode, no jnp fallback).

Each phase prints one JSON line: wall seconds, seconds spent in XLA's
backend compiler, persistent-cache hits and misses, and the device's
peak bytes in use.  These are set-up timings of a smoke run, not
performance numbers.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Without a TPU as JAX's default backend the script exits non-zero and
prints no result.  The compile cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``.jax_cache/`` beside this file; the archive
is built in ``.smoke_archive/`` beside it and removed at exit.
"""

from __future__ import annotations

import argparse
import contextlib
import http.client
import json
import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.catalog import Catalog  # noqa: E402
from repro.core import fm301  # noqa: E402
from repro.etl import LiveFeed, generate_raw_archive, ingest, level2  # noqa: E402
from repro.etl.generator import live_scan_feed  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.radar.incremental import incremental_product  # noqa: E402
from repro.radar.products import ProductRequest, compute_product  # noqa: E402
from repro.radar.qpe import qpe_from_volumes  # noqa: E402
from repro.radar.qvp import qvp_from_volumes  # noqa: E402
from repro.serve.http import ArchiveServer, ArchiveService, decode_payload  # noqa: E402
from repro.store import ObjectStore, Repository  # noqa: E402

VCP = "VCP-212"
SITES = ("KVNX", "KTLX")
N_SCANS = 6                     # 6 x 270 s: 27 minutes of storm mode
NY = NX = 240                   # the products' default 240 x 240 grid
WORK = ROOT / ".smoke_archive"
KERNELS = ("qvp_reduce_pallas", "zr_accum_pallas", "grid_map_pallas",
           "grid_update_pallas")
PRODUCTS = {
    "qvp": f"/products/qvp?repo=KVNX&vcp={VCP}&sweep=0",
    "qpe": f"/products/qpe?repo=KVNX&vcp={VCP}&sweep=0",
    "cappi": f"/products/cappi?repo=KVNX&vcp={VCP}&ny={NY}&nx={NX}",
    "column_max": f"/products/column_max?repo=KVNX&vcp={VCP}"
                  f"&ny={NY}&nx={NX}",
    "mosaic": f"/products/mosaic?ny={NY}&nx={NX}",
}

# Tolerances against the off-chip references.  The archive holds DBZH at
# 0.01 dBZ (fm301.MOMENT_PACKING); every bound below sits under that.
# - QVP: the chip sums a radial's 720 azimuths in its own order; any
#   order of a float32 sum of n terms lies within n * eps32 * max|x| of
#   another, so atol is that bound (about 6e-3 dBZ here), computed from
#   the data.
# - QPE: the chip evaluates pow as exp/log in its own instructions.  A
#   0.01 dBZ step moves a Marshall-Palmer rate by 0.14 %; 1e-4 relative
#   is 14 times finer than the data resolves.
# - Gridded products (nearest neighbour) copy one gate per cell; only
#   the chip's divide by the unit weight may round: 1e-6 relative.
QPE_RTOL, QPE_ATOL_MM = 1e-4, 1e-5
GRID_RTOL = 1e-6


class SmokeFailure(Exception):
    """A phase produced a wrong or missing answer."""


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events (fired on whichever thread compiles)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration_secs

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self):
        with self._lock:
            return self.compile_s, self.hits, self.misses


class KernelLog:
    """Records the (shapes, options) of every call the product path makes
    into the four Pallas wrappers that ``repro.kernels.ops`` dispatches to;
    the wrapped functions run unchanged."""

    def __init__(self) -> None:
        self.calls = {name: {} for name in KERNELS}
        for name in KERNELS:
            setattr(ops, name, self._wrap(name, getattr(ops, name)))

    def _wrap(self, name, fn):
        def recorded(*args, **kwargs):
            specs = tuple(jax.ShapeDtypeStruct(
                np.shape(a), jax.dtypes.canonicalize_dtype(np.result_type(a)))
                for a in args)
            key = (tuple((s.shape, str(s.dtype)) for s in specs),
                   tuple(sorted(kwargs.items())))
            self.calls[name].setdefault(key, (fn, specs, kwargs))
            return fn(*args, **kwargs)
        return recorded


def emit(doc) -> None:
    print(json.dumps(doc), flush=True)


@contextlib.contextmanager
def phase(name, meter):
    """Time the block and report it; the block may add fields to the
    yielded dict.  A block that raises reports nothing."""
    t0, before = time.perf_counter(), meter.snapshot()
    extra = {}
    yield extra
    compile_s, hits, misses = meter.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    emit({"phase": name, "seconds": time.perf_counter() - t0,
          "compile_seconds": compile_s - before[0],
          "cache_hits": hits - before[1], "cache_misses": misses - before[2],
          "peak_bytes_in_use": stats.get("peak_bytes_in_use"), **extra})


def check(name, got, want, *, rtol=0.0, atol=0.0) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise SmokeFailure(f"{name}: shape {got.shape} != {want.shape}")
    nan_got, nan_want = np.isnan(got), np.isnan(want)
    if not np.array_equal(nan_got, nan_want):
        raise SmokeFailure(f"{name}: NaN pattern differs at "
                           f"{int((nan_got != nan_want).sum())} values")
    g = got[~nan_got].astype(np.float64)
    w = want[~nan_want].astype(np.float64)
    err = np.abs(g - w)
    bad = err > atol + rtol * np.abs(w)
    if bad.any():
        raise SmokeFailure(f"{name}: {int(bad.sum())} values outside "
                           f"rtol={rtol} atol={atol}; max |err| "
                           f"{err.max()}")
    emit({"check": name, "values": int(got.size), "nan": int(nan_got.sum()),
          "max_abs_err": float(err.max()) if err.size else 0.0,
          "rtol": rtol, "atol": atol})


def build_archive(seed: int):
    """Two full-geometry sites, raw files -> ingest -> one catalog."""
    catalog = Catalog.create(str(WORK / "catalog"))
    repos, raw = {}, {}
    for i, site in enumerate(SITES):
        raw[site] = ObjectStore(str(WORK / f"raw-{site}"))
        generate_raw_archive(raw[site], site_id=site, vcp_name=VCP,
                             n_scans=N_SCANS, seed=seed + i)
        repos[site] = Repository.create(str(WORK / f"store-{site}"))
        ingest(raw[site], repos[site], workers=4, catalog=catalog,
               repo_id=site)
    return catalog, repos, raw


def fetch_products(catalog, meter):
    """GET every product from a fresh in-process server; (bodies, secs,
    compile secs) per product."""
    service = ArchiveService(catalog)
    bodies, seconds, compile_s = {}, {}, {}
    try:
        with ArchiveServer(service) as server:
            host, port = server.address

            def client():
                for kind, path in PRODUCTS.items():
                    c0 = meter.snapshot()[0]
                    t0 = time.perf_counter()
                    conn = http.client.HTTPConnection(host, port, timeout=900)
                    try:
                        conn.request("GET", path)
                        resp = conn.getresponse()
                        body = resp.read()
                    finally:
                        conn.close()
                    if resp.status != 200:
                        raise SmokeFailure(f"GET {path} -> {resp.status}: "
                                           f"{body[:500]!r}")
                    seconds[kind] = time.perf_counter() - t0
                    compile_s[kind] = meter.snapshot()[0] - c0
                    bodies[kind] = body

            with ThreadPoolExecutor(1, thread_name_prefix="client") as pool:
                pool.submit(client).result()
    finally:
        service.close()
    return bodies, seconds, compile_s


def sweep0_volumes(raw_store):
    """The raw files decoded by the reference's own reader, sweep 0 only."""
    out = []
    for key in sorted(raw_store.list(f"KVNX/{VCP}/")):
        vol = level2.decode_volume(raw_store.get(key))
        out.append({"time": vol["time"], "sweeps": vol["sweeps"][:1]})
    return out


def check_references(bodies, catalog, repos, raw) -> None:
    arrays = {kind: decode_payload(body)[1] for kind, body in bodies.items()}
    vols = sweep0_volumes(raw["KVNX"])
    field = np.stack([v["sweeps"][0]["moments"]["DBZH"] for v in vols])
    n_az = field.shape[1]
    qvp = qvp_from_volumes(vols, sweep=0, quality_moment=None)
    check("qvp", arrays["qvp"]["profile"], qvp.profile,
          atol=n_az * float(np.finfo(np.float32).eps)
          * float(np.nanmax(np.abs(field))))
    check("qvp.times", arrays["qvp"]["times"], qvp.times)
    qpe = qpe_from_volumes(vols, sweep=0)
    check("qpe", arrays["qpe"]["accum_mm"], qpe.accum_mm,
          rtol=QPE_RTOL, atol=QPE_ATOL_MM)

    # the gridded products again, with the jnp oracles on the host CPU;
    # a process-wide default, since the mosaic fans out over threads
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    try:
        session = repos["KVNX"].readonly_session()
        try:
            for kind in ("cappi", "column_max"):
                want = compute_product(session, ProductRequest(
                    kind=kind, vcp=VCP, ny=NY, nx=NX, mode="ref"))
                check(kind, arrays[kind]["values"], want.values,
                      rtol=GRID_RTOL)
        finally:
            session.close()
        mosaic = compute_product(catalog, ProductRequest(
            kind="mosaic", ny=NY, nx=NX, mode="ref"))
    finally:
        jax.config.update("jax_default_device", None)
    check("mosaic", arrays["mosaic"]["composite"], mosaic.composite,
          rtol=GRID_RTOL)
    for site in SITES:
        check(f"mosaic.{site}", arrays["mosaic"][f"{site}/values"],
              mosaic.results[site].values, rtol=GRID_RTOL)


def check_custom_calls(log: KernelLog) -> None:
    for name, calls in log.calls.items():
        if not calls:
            raise SmokeFailure(f"the product path never called {name}")
        for fn, specs, kwargs in calls.values():
            if kwargs.get("interpret"):
                raise SmokeFailure(f"{name} ran in interpret mode")
            text = fn.lower(*specs, **kwargs).compile().as_text()
            if "tpu_custom_call" not in text:
                raise SmokeFailure(f"{name}{[s.shape for s in specs]} "
                                   "compiled without a Mosaic kernel")
        emit({"kernel": name, "programs": len(calls),
              "shapes": [[list(s.shape) for s in specs]
                         for _fn, specs, _kw in calls.values()]})


def stream_one_scan(seed: int, catalog, repos) -> None:
    repo = repos["KVNX"]
    req = ProductRequest(kind="cappi", vcp=VCP, ny=NY, nx=NX)
    inc = incremental_product(repo, req)
    inc.update()                                    # state for 6 scans
    feed = LiveFeed(repo, live_scan_feed(site_id="KVNX", vcp_name=VCP,
                                         seed=seed, start=N_SCANS),
                    catalog=catalog, repo_id="KVNX")
    if len(feed.ingest_next(1)) != 1:
        raise SmokeFailure("live feed committed no scan")
    report = inc.update()                           # patch in the 7th
    if report.n_new_scans != 1:
        raise SmokeFailure(f"incremental update saw {report.n_new_scans} "
                           "new scans, expected 1")
    state = inc.read()
    session = repo.readonly_session()
    try:
        scratch = compute_product(session, req.with_options(grid=state.grid))
    finally:
        session.close()
    if not np.array_equal(state.values, scratch.values, equal_nan=True):
        raise SmokeFailure("incremental CAPPI differs from the rebuild")
    emit({"check": "incremental_cappi", "scans": int(state.values.shape[0]),
          "cells_computed": report.cells_computed,
          "cells_full": report.cells_full, "bitwise": True})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="storm simulator seed (KTLX uses seed + 1)")
    args = parser.parse_args(argv)

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX default backend is "
                 f"{jax.default_backend()!r}); nothing was run")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = jax.devices()[0]
    emit({"device": device.device_kind, "platform": device.platform,
          "count": len(jax.devices()), "jax": jax.__version__,
          "cache_dir": jax.config.jax_compilation_cache_dir})

    meter, log = CompileMeter(), KernelLog()
    shutil.rmtree(WORK, ignore_errors=True)
    try:
        vcp = fm301.VCPS[VCP]
        with phase("archive", meter) as out:
            catalog, repos, raw = build_archive(args.seed)
            out.update(sites=list(SITES), scans=N_SCANS, cuts=vcp.n_sweeps,
                       azimuths=vcp.n_azimuth, gates=vcp.n_gates)

        bodies = {}
        for rnd in ("serve_cold", "serve_warm"):
            with phase(rnd, meter) as out:
                got, secs, comp = fetch_products(catalog, meter)
                if bodies and got != bodies:
                    raise SmokeFailure("a fresh service returned different "
                                       "bytes for the same products")
                bodies = got
                out.update(product_seconds=secs,
                           product_compile_seconds=comp,
                           body_bytes={k: len(v) for k, v in got.items()})

        with phase("references", meter):
            check_references(bodies, catalog, repos, raw)
        with phase("streaming", meter):
            stream_one_scan(args.seed, catalog, repos)
        with phase("kernels", meter):
            check_custom_calls(log)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)

    emit({"ok": True, "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
