"""Quasi-Vertical Profiles from a Radar DataTree (paper §5.1).

A QVP (Ryzhkov et al. 2016) composites azimuthal means of a high-elevation
sweep over time, giving a time–height view of storm microphysics.  Against
the DataTree store this is: one chunk-aligned lazy read of exactly the
(sweep, moment[, quality]) arrays requested, then one fused reduction —
no per-file decoding, which is where the paper's ~100× comes from.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from ..kernels import ops
from ..store import Session
from . import geometry
from ._selection import TimeSliceLike, as_time_slice


@dataclass
class QVPResult:
    """A quasi-vertical profile: (time, height) matrix plus axes."""
    profile: np.ndarray          # (time, range) azimuthal means
    times: np.ndarray            # (time,) epoch seconds
    height_m: np.ndarray         # (range,) beam height AGL
    moment: str
    elevation_deg: float

    @property
    def shape(self):
        return self.profile.shape


def qvp_from_session(
    session: Session,
    *,
    vcp: str,
    sweep: int,
    moment: str = "DBZH",
    quality_moment: Optional[str] = "RHOHV",
    quality_min: float = 0.85,
    time_slice: TimeSliceLike = None,
    mode: str = "auto",
) -> QVPResult:
    """Deprecated alias for the unified product API.

    Use ``compute_product(session, ProductRequest(kind="qvp", ...))``
    from :mod:`repro.radar.products`; results are bitwise identical.
    """
    warnings.warn(
        "qvp_from_session is deprecated; use repro.radar.products."
        "compute_product with ProductRequest(kind='qvp')",
        DeprecationWarning, stacklevel=2,
    )
    from .products import ProductRequest, compute_product
    return compute_product(session, ProductRequest(
        kind="qvp", vcp=vcp, sweep=sweep, moment=moment,
        quality_moment=quality_moment, quality_min=quality_min,
        time_slice=time_slice, mode=mode,
    ))


def _qvp_from_session(
    session: Session,
    *,
    vcp: str,
    sweep: int,
    moment: str = "DBZH",
    quality_moment: Optional[str] = "RHOHV",
    quality_min: float = 0.85,
    time_slice: TimeSliceLike = None,
    mode: str = "auto",
) -> QVPResult:
    # the QVP implementation (dispatched via repro.radar.products):
    # one chunk-aligned lazy read of exactly the requested arrays, then
    # one fused reduction.  ``time_slice`` accepts a slice or an
    # (i0, i1) index pair as produced by the catalog query planner.
    time_slice = as_time_slice(time_slice)
    base = f"{vcp}/sweep_{sweep}"
    # every array the profile needs, one asynchronous prefetch plan:
    # time + field + quality + range stream in batched while the first
    # demand read below waits only on its own chunks
    items = [(f"{vcp}/time", (time_slice,)),
             (f"{base}/{moment}", (time_slice,)),
             f"{base}/range"]
    if quality_moment is not None:
        items.append((f"{base}/{quality_moment}", (time_slice,)))
    session.prefetch(items, wait=False)
    field_arr = session.array(f"{base}/{moment}")
    times = session.array(f"{vcp}/time")[time_slice]
    field = field_arr[time_slice]                     # chunk-aligned read
    quality = None
    if quality_moment is not None and session.has_array(
        f"{base}/{quality_moment}"
    ):
        quality = session.array(f"{base}/{quality_moment}")[time_slice]

    profile = ops.to_host(ops.qvp_reduce, field, quality,
                          quality_min=quality_min, mode=mode)
    rng_m = session.array(f"{base}/range").read()
    elev = float(session.group_attrs(base)["fixed_angle"])
    height = geometry.beam_height_m(rng_m, elev)
    return QVPResult(profile, np.asarray(times), np.asarray(height), moment,
                     elev)


def qvp_from_volumes(
    volumes,
    *,
    sweep: int,
    moment: str = "DBZH",
    quality_moment: Optional[str] = "RHOHV",
    quality_min: float = 0.85,
) -> QVPResult:
    """File-based QVP baseline.

    The Py-ART-style workflow the paper compares
    against.  Each decoded volume is processed scan-by-scan with plain
    numpy — including all the moments that were decoded just to be thrown
    away, as happens with real Level-II files."""
    profiles, times = [], []
    elev, rng_m = 0.0, None
    for vol in volumes:
        sw = vol["sweeps"][sweep]
        field = sw["moments"][moment]
        valid = np.isfinite(field)
        if quality_moment is not None and quality_moment in sw["moments"]:
            q = sw["moments"][quality_moment]
            valid &= np.isfinite(q) & (q >= quality_min)
        x = np.where(valid, field, 0.0)
        count = valid.sum(axis=0).astype(np.float32)
        mean = x.sum(axis=0) / np.maximum(count, 1.0)
        mean = np.where(count >= 0.1 * field.shape[0], mean, np.nan)
        profiles.append(mean.astype(np.float32))
        times.append(vol["time"])
        elev = sw["elevation"]
        rng_m = sw["range"]
    height = geometry.beam_height_m(rng_m, elev)
    return QVPResult(np.stack(profiles), np.asarray(times),
                     np.asarray(height), moment, elev)
