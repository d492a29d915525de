"""Build a configuration's archive from the seed: field on the device,
archive through the program's own writer.

The storm field is a float32 ``jax.numpy`` copy of the DBZH field of
``repro.etl.generator.StormSimulator`` (convective cells advecting with
the mean wind and capped by an echo top, a stratiform background with a
melting-layer bright band, gate noise), computed on the device by one
jitted program per block of scans, quantized on the device to the archive's
packing step as ingest would leave it, and copied to the host once.
Time runs from the configuration's ``t0`` so float32 keeps it exact.

The host arrays then go through ``RadarArchive.append_scan`` into one
transaction per site (one commit, chunks encoded by as many threads as
the host has cores) and are registered in one ``Catalog``.  The same
host arrays are what the reference reads.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

EARTH_RADIUS_M = 6371000.0
KE = 4.0 / 3.0
N_CELLS = 6
MELTING_LAYER_M = 3200.0


@dataclass
class SiteData:
    """One site's generated archive content, as the reference reads it."""

    site: Dict
    times: np.ndarray            # (T,) float64 epoch seconds
    elevations: List[float]      # per stored cut
    azimuth: List[np.ndarray]    # per cut, (A,) float32 degrees
    range_m: List[np.ndarray]    # per cut, (R,) float32 metres
    dbzh: List[np.ndarray]       # per cut, (T, A, R) float32


def _key(seed: int, salt: int):
    key = jax.random.key(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def _beam_height(r, elev_deg):
    el = jnp.deg2rad(elev_deg)
    return (jnp.sqrt(r**2 + (KE * EARTH_RADIUS_M) ** 2
                     + 2.0 * r * KE * EARTH_RADIUS_M * jnp.sin(el))
            - KE * EARTH_RADIUS_M)


def _field(key, rel_t, t_index, elev, n_az: int, n_gates: int,
           gate_m: float, first_gate_m: float, step: float):
    """(T, A, R) quantized DBZH of one cut at scan times ``rel_t`` (s
    after t0); ``t_index`` numbers the scans for their gate noise."""
    ks = jax.random.split(key, 8)
    wind_x = jax.random.uniform(ks[0], (), minval=5.0, maxval=15.0)
    wind_y = jax.random.uniform(ks[1], (), minval=-5.0, maxval=5.0)
    x0 = jax.random.uniform(ks[2], (N_CELLS,), minval=-80e3, maxval=80e3)
    y0 = jax.random.uniform(ks[3], (N_CELLS,), minval=-80e3, maxval=80e3)
    vel = jax.random.normal(ks[4], (2, N_CELLS)) * 2.0
    vx, vy = wind_x + vel[0], wind_y + vel[1]
    peak = jax.random.uniform(ks[5], (N_CELLS,), minval=42.0, maxval=62.0)
    radius = jax.random.uniform(ks[6], (N_CELLS,), minval=4e3, maxval=12e3)
    top_growth = jax.random.uniform(ks[7], (2, N_CELLS))
    top = 8e3 + 6e3 * top_growth[0]
    growth = 1e-4 + 5e-4 * top_growth[1]

    az = jnp.deg2rad((jnp.arange(n_az, dtype=jnp.float32) + 0.5)
                     * (360.0 / n_az))[:, None]
    rng = (first_gate_m
           + jnp.arange(n_gates, dtype=jnp.float32) * gate_m)[None, :]
    x, y = rng * jnp.sin(az), rng * jnp.cos(az)
    h = _beam_height(rng, elev)
    noise_key = jax.random.fold_in(key, 1 + jnp.round(elev * 100).astype(
        jnp.int32))

    def one(t, i):
        dbz = jnp.full((n_az, n_gates), -12.0, jnp.float32)
        for c in range(N_CELLS):
            cx = (x0[c] + vx[c] * t + 80e3) % 160e3 - 80e3
            cy = (y0[c] + vy[c] * t + 80e3) % 160e3 - 80e3
            amp = peak[c] * (0.75 + 0.25 * jnp.sin(growth[c] * t))
            d2 = (x - cx) ** 2 + (y - cy) ** 2
            vert = jnp.clip(1.0 - h / top[c], 0.0, 1.0)
            dbz = jnp.maximum(dbz, amp * jnp.exp(-d2 / (2 * radius[c] ** 2))
                              * vert)
        strat = 18.0 * jnp.exp(-((h - 0.6 * MELTING_LAYER_M) / 4000.0) ** 2)
        bright = 7.0 * jnp.exp(-((h - MELTING_LAYER_M) / 350.0) ** 2)
        dbz = jnp.maximum(dbz, strat + bright)
        dbz = dbz + 0.7 * jax.random.normal(jax.random.fold_in(noise_key, i),
                                            dbz.shape)
        packed = jnp.clip(jnp.round(dbz / step), -32767, 32767)
        return packed.astype(jnp.int16).astype(jnp.float32) * jnp.float32(step)

    return jax.vmap(one)(rel_t, t_index)


_field_jit = jax.jit(_field, static_argnums=(4, 5, 6, 7, 8))
BLOCK_SCANS = 40    # scans per generating call: one program for every block


def _cut_axes(cut: Dict):
    """(azimuth, range) of one cut's geometry, float32 degrees and metres."""
    n_az = int(cut["n_azimuth"])
    az = (np.arange(n_az, dtype=np.float32) + 0.5) * np.float32(360.0 / n_az)
    rng = (np.float32(cut["first_gate_m"])
           + np.arange(int(cut["n_gates"]), dtype=np.float32)
           * np.float32(cut["gate_m"]))
    return az, rng


def generate(cfg: Dict, seed: int) -> Dict[str, SiteData]:
    """Every site's archive content for ``seed``, made on the device in
    blocks of scans (all dispatched before the first is copied back).
    Each stored cut has its own geometry (``cfg["cuts"]``)."""
    n_scans = int(cfg["n_scans"])
    dt = float(cfg["vcp"]["interval_s"])
    times = float(cfg["t0"]) + np.arange(n_scans) * dt
    step = float(cfg["packing_step"]["DBZH"])
    block = min(BLOCK_SCANS, n_scans)
    starts = list(range(0, n_scans, block))
    elevs = [float(e) for e in cfg["elevations"]]
    cuts = [cfg["cuts"][str(e)] for e in cfg["elevations"]]
    out = {}
    for site in cfg["sites"]:
        key = _key(seed, int(site["seed_offset"]))
        host = [np.empty((n_scans, int(c["n_azimuth"]), int(c["n_gates"])),
                         np.float32) for c in cuts]
        pending = []
        for ci, (elev, cut) in enumerate(zip(elevs, cuts)):
            static = (int(cut["n_azimuth"]), int(cut["n_gates"]),
                      float(cut["gate_m"]), float(cut["first_gate_m"]), step)
            for s in starts:
                idx = np.arange(s, s + block)     # the last block runs past
                pending.append((ci, s, _field_jit(
                    key, (idx * dt).astype(np.float32), idx.astype(np.int32),
                    np.float32(elev), *static)))
        for ci, s, dev in pending:
            n = min(block, n_scans - s)
            host[ci][s:s + n] = np.asarray(dev)[:n]
        axes = [_cut_axes(c) for c in cuts]
        out[site["site_id"]] = SiteData(site, times, elevs,
                                        [a for a, _ in axes],
                                        [r for _, r in axes], host)
    return out


def build(cfg: Dict, data: Dict[str, SiteData], workdir: str):
    """Write every site through ``RadarArchive.append_scan`` (one commit
    per site) and register them in one catalog; returns the catalog."""
    from repro.catalog import Catalog
    from repro.core import fm301
    from repro.core.datatree import RadarArchive
    from repro.store import Repository
    from repro.store.codecs import get_codec

    get_codec(cfg["codec"])           # a missing codec fails here, loudly
    if int(cfg["range_chunk"]) != RadarArchive.RANGE_CHUNK:
        raise ValueError(f"range_chunk {cfg['range_chunk']} is not the "
                         f"archive's {RadarArchive.RANGE_CHUNK}")
    v, first = cfg["vcp"], cfg["cuts"][str(cfg["elevations"][0])]
    # the archive takes each sweep's shape from its arrays; the VCP
    # record carries the first cut's
    vcp = fm301.VCPDef(int(v["vcp_id"]), tuple(cfg["elevations"]),
                       int(first["n_azimuth"]), int(first["n_gates"]),
                       float(first["gate_m"]), float(v["interval_s"]),
                       tuple(cfg["moments"]))
    catalog = Catalog.create(os.path.join(workdir, "catalog"))
    for site_id, sd in data.items():
        s = sd.site
        site = fm301.RadarSite(site_id, float(s["latitude"]),
                               float(s["longitude"]), float(s["altitude_m"]),
                               s.get("instrument_name", ""))
        repo = Repository.create(os.path.join(workdir, f"store-{site_id}"))
        archive = RadarArchive(repo, codec=cfg["codec"],
                               time_chunk=int(cfg["time_chunk"]))
        tx = repo.writable_session()
        tx.encode_workers = max(1, os.cpu_count() or 1)
        for i, t in enumerate(sd.times):
            archive.append_scan({
                "site": site, "vcp": vcp, "time": float(t),
                "sweeps": [{"elevation": e, "azimuth": sd.azimuth[c],
                            "range": sd.range_m[c],
                            "moments": {"DBZH": sd.dbzh[c][i]}}
                           for c, e in enumerate(sd.elevations)],
            }, tx=tx, commit=False)
        tx.commit(f"chipbench archive {site_id}: {len(sd.times)} scans")
        catalog.register_repository(repo, repo_id=site_id)
    return catalog


__all__ = ["SiteData", "generate", "build"]
