"""Quantitative Precipitation Estimation (paper §5.3).

Marshall–Palmer Z–R over the lowest sweep, time-integrated to accumulated
precipitation.  The DataTree path reads only DBZH for the requested time
window and runs the fused Z–R+integration kernel; the file-based baseline
decodes complete volumes scan-by-scan.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..kernels import ops
from ..store import Session
from ._selection import TimeSliceLike, as_time_slice


@dataclass
class QPEResult:
    """Accumulated rainfall map plus its polar axes."""
    accum_mm: np.ndarray         # (azimuth, range)
    total_hours: float
    n_scans: int
    azimuth: np.ndarray
    range_m: np.ndarray


def _dt_weights(times: np.ndarray) -> np.ndarray:
    """Integration weight per scan: midpoint rule over scan intervals."""
    t = np.asarray(times, dtype=np.float64)
    if t.size == 1:
        return np.array([300.0], dtype=np.float32)
    dt = np.empty_like(t)
    dt[1:-1] = (t[2:] - t[:-2]) / 2.0
    dt[0] = t[1] - t[0]
    dt[-1] = t[-1] - t[-2]
    return dt.astype(np.float32)


def qpe_from_session(
    session: Session,
    *,
    vcp: str,
    sweep: int = 0,
    moment: str = "DBZH",
    time_slice: TimeSliceLike = None,
    a: float = 200.0,
    b: float = 1.6,
    mode: str = "auto",
) -> QPEResult:
    """Deprecated alias for the unified product API.

    Use ``compute_product(session, ProductRequest(kind="qpe", ...))``
    from :mod:`repro.radar.products`; results are bitwise identical.
    """
    warnings.warn(
        "qpe_from_session is deprecated; use repro.radar.products."
        "compute_product with ProductRequest(kind='qpe')",
        DeprecationWarning, stacklevel=2,
    )
    from .products import ProductRequest, compute_product
    return compute_product(session, ProductRequest(
        kind="qpe", vcp=vcp, sweep=sweep, moment=moment,
        time_slice=time_slice, a=a, b=b, mode=mode,
    ))


def _qpe_from_session(
    session: Session,
    *,
    vcp: str,
    sweep: int = 0,
    moment: str = "DBZH",
    time_slice: TimeSliceLike = None,
    a: float = 200.0,
    b: float = 1.6,
    mode: str = "auto",
) -> QPEResult:
    # the QPE implementation (dispatched via repro.radar.products).
    # ``time_slice`` accepts a slice or a planner (i0, i1) index pair.
    time_slice = as_time_slice(time_slice)
    base = f"{vcp}/sweep_{sweep}"
    times = session.array(f"{vcp}/time")[time_slice]
    dbz = session.array(f"{base}/{moment}")[time_slice]
    dt_s = _dt_weights(times)
    accum = ops.to_host(ops.zr_accum, dbz, dt_s, a=a, b=b, mode=mode)
    return QPEResult(
        accum_mm=accum,
        total_hours=float(dt_s.sum() / 3600.0),
        n_scans=len(times),
        azimuth=session.array(f"{base}/azimuth").read(),
        range_m=session.array(f"{base}/range").read(),
    )


def qpe_from_volumes(
    volumes,
    *,
    sweep: int = 0,
    moment: str = "DBZH",
    a: float = 200.0,
    b: float = 1.6,
) -> QPEResult:
    """File-based baseline: per-scan numpy Z–R then accumulate."""
    times = np.asarray([v["time"] for v in volumes])
    dt_s = _dt_weights(times)
    accum = None
    for vol, dt in zip(volumes, dt_s):
        sw = vol["sweeps"][sweep]
        dbz = sw["moments"][moment]
        dbz_c = np.clip(dbz, 5.0, 53.0)
        z_lin = np.power(10.0, dbz_c / 10.0)
        rate = np.power(z_lin / a, 1.0 / b)
        rate = np.where(np.isfinite(dbz) & (dbz >= 5.0), rate, 0.0)
        term = rate * (dt / 3600.0)
        accum = term if accum is None else accum + term
    sw0 = volumes[0]["sweeps"][sweep]
    return QPEResult(
        accum_mm=accum.astype(np.float32),
        total_hours=float(dt_s.sum() / 3600.0),
        n_scans=len(volumes),
        azimuth=sw0["azimuth"],
        range_m=sw0["range"],
    )
