"""The plain reference, the control, and the comparison that decides
``correct``.

Nothing here imports the program or takes anything it made.  The
reference answers a product request from the generated arrays alone
(:class:`~chipbench.archive.SiteData`), with copies of the semantics of
record, written plainly:

- QVP: the file-based baseline ``qvp_from_volumes`` (per scan, numpy,
  masked azimuthal mean, NaN below 10 % valid azimuths; no quality gate);
- QPE: ``qpe_from_volumes`` (per scan Marshall-Palmer Z-R, clipped to
  5..53 dBZ, midpoint-rule scan weights, float32 accumulation).

The HTTP body is decoded here too (``RPRD`` frame: magic, u32 header
length, canonical JSON header, C-order arrays).

The control is the same reference with its arithmetic on the data done
in bfloat16, the precision below the configuration's float32.
"""

from __future__ import annotations

import json
import struct
from typing import Any, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlsplit

import numpy as np

EARTH_RADIUS_M = 6371000.0
KE = 4.0 / 3.0
MIN_VALID_FRACTION = 0.1
QPE_FLOOR_MM = 1e-3            # relative QPE error is taken against this
                               # much rain at least (one 270 s scan at the
                               # 5 dBZ threshold already leaves 5.6e-3 mm)

Answer = Tuple[Dict[str, Any], Dict[str, np.ndarray]]


# ---------------------------------------------------------------------------
# The wire format
# ---------------------------------------------------------------------------

def decode_body(body: bytes) -> Answer:
    if body[:4] != b"RPRD":
        raise ValueError("not an RPRD frame")
    (hlen,) = struct.unpack(">I", body[4:8])
    header = json.loads(body[8:8 + hlen])
    arrays, off = {}, 8 + hlen
    for spec in header["arrays"]:
        dt = np.dtype(spec["dtype"])
        shape = tuple(spec["shape"])
        n = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        arrays[spec["name"]] = np.frombuffer(body[off:off + n],
                                             dtype=dt).reshape(shape)
        off += n
    if off != len(body):
        raise ValueError(f"frame has {len(body) - off} trailing bytes")
    return header["doc"], arrays


# ---------------------------------------------------------------------------
# Beam geometry
# ---------------------------------------------------------------------------

def beam_height_m(range_m, elev_deg: float):
    """Height of the beam centre above the radar (4/3-earth model)."""
    el = np.deg2rad(elev_deg)
    r = np.asarray(range_m, dtype=np.float64)
    return (np.sqrt(r**2 + (KE * EARTH_RADIUS_M) ** 2
                    + 2.0 * r * KE * EARTH_RADIUS_M * np.sin(el))
            - KE * EARTH_RADIUS_M)


# ---------------------------------------------------------------------------
# Arithmetic on the data, in the configuration's precision or the control's
# ---------------------------------------------------------------------------

class _F32:
    """float32 numpy, scan by scan: the semantics of record."""

    @staticmethod
    def qvp(field):                                   # (T, A, R)
        out = []
        for f in field:
            valid = np.isfinite(f)
            x = np.where(valid, f, 0.0)
            count = valid.sum(axis=0).astype(np.float32)
            mean = x.sum(axis=0) / np.maximum(count, 1.0)
            mean = np.where(count >= MIN_VALID_FRACTION * f.shape[0], mean,
                            np.nan)
            out.append(mean.astype(np.float32))
        return np.stack(out) if out else np.zeros((0, field.shape[2]),
                                                  np.float32)

    @staticmethod
    def qpe(dbz, dt_s, a, b):                         # (T, A, R), (T,)
        accum = np.zeros(dbz.shape[1:], np.float32)
        for d, dt in zip(dbz, dt_s):
            dbz_c = np.clip(d, 5.0, 53.0)
            rate = np.power(np.power(10.0, dbz_c / 10.0) / a, 1.0 / b)
            rate = np.where(np.isfinite(d) & (d >= 5.0), rate, 0.0)
            accum = accum + rate * (dt / 3600.0)
        return accum.astype(np.float32)


class _BF16:
    """The control: the same arithmetic with the data in bfloat16."""

    @staticmethod
    def _jnp():
        import jax.numpy as jnp
        return jnp

    @classmethod
    def qvp(cls, field):
        jnp = cls._jnp()
        f = jnp.asarray(field).astype(jnp.bfloat16)
        valid = jnp.isfinite(f)
        x = jnp.where(valid, f, 0)
        count = jnp.sum(valid, axis=1).astype(jnp.bfloat16)
        mean = jnp.sum(x, axis=1, dtype=jnp.bfloat16) / jnp.maximum(count, 1)
        mean = jnp.where(count >= MIN_VALID_FRACTION * f.shape[1], mean,
                         jnp.nan)
        return np.asarray(mean.astype(jnp.float32))

    @classmethod
    def qpe(cls, dbz, dt_s, a, b):
        jnp = cls._jnp()
        d = jnp.asarray(dbz).astype(jnp.bfloat16)
        dbz_c = jnp.clip(d, 5, 53)
        rate = jnp.power(jnp.power(jnp.bfloat16(10), dbz_c / 10) / a, 1 / b)
        rate = jnp.where(jnp.isfinite(d) & (d >= 5), rate, 0)
        w = (jnp.asarray(dt_s) / 3600.0).astype(jnp.bfloat16)[:, None, None]
        acc = jnp.sum((rate * w).astype(jnp.bfloat16), axis=0,
                      dtype=jnp.bfloat16)
        return np.asarray(acc.astype(jnp.float32))


PRECISIONS = {"float32": _F32, "bfloat16": _BF16}


def dt_weights(times: np.ndarray) -> np.ndarray:
    """Midpoint-rule integration weight per scan, seconds."""
    t = np.asarray(times, dtype=np.float64)
    if t.size == 1:
        return np.array([300.0], dtype=np.float32)
    dt = np.empty_like(t)
    dt[1:-1] = (t[2:] - t[:-2]) / 2.0
    dt[0] = t[1] - t[0]
    dt[-1] = t[-1] - t[-2]
    return dt.astype(np.float32)


# ---------------------------------------------------------------------------
# Answers
# ---------------------------------------------------------------------------

def parse(path: str) -> Tuple[str, Dict[str, str]]:
    url = urlsplit(path)
    kind = url.path.rstrip("/").split("/")[-1]
    return kind, {k: v[0] for k, v in parse_qs(url.query).items()}


class Reference:
    """Answers product requests from the generated arrays."""

    def __init__(self, data: Dict[str, Any]) -> None:
        self.data = data            # site_id -> SiteData

    def answer(self, path: str, precision: str = "float32") -> Answer:
        kind, p = parse(path)
        math = PRECISIONS[precision]
        sd = self.data[p["repo"]]
        tsl = slice(int(p["i0"]), int(p["i1"]))
        times = sd.times[tsl]
        cut = int(p.get("sweep", 0))
        if kind == "qvp":
            elev = sd.elevations[cut]
            return ({"product": "qvp", "moment": "DBZH",
                     "elevation_deg": float(elev)},
                    {"profile": math.qvp(sd.dbzh[cut][tsl]),
                     "times": times,
                     "height_m": beam_height_m(sd.range_m[cut], elev)})
        if kind == "qpe":
            dt = dt_weights(times)
            return ({"product": "qpe",
                     "total_hours": float(dt.sum() / 3600.0),
                     "n_scans": int(times.size)},
                    {"accum_mm": math.qpe(sd.dbzh[cut][tsl], dt,
                                          float(p.get("a", 200.0)),
                                          float(p.get("b", 1.6))),
                     "azimuth": sd.azimuth[cut], "range_m": sd.range_m[cut]})
        raise ValueError(f"the reference has no product {kind!r}")


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

#: the number each product kind's values are compared by
NUMBER = {"qvp": "qvp_max_abs_dbz", "qpe": "qpe_max_rel"}
#: the array holding the values that number is taken over
VALUES = {"qvp": ("profile",), "qpe": ("accum_mm",)}


def compare(kind: str, got: Answer, want: Answer) -> Dict[str, float]:
    """The compared numbers for one answer: the kind's value number (the
    largest gap on the values, absolute or, for QPE, relative), and
    ``exact_mismatches``: doc fields, array names, shapes, NaN cells and
    axes that differ (exact by construction, so any difference counts)."""
    gdoc, garr = got
    wdoc, warr = want
    mism = 0
    for k, v in wdoc.items():
        if k not in gdoc or gdoc[k] != v:
            mism += 1
    if set(garr) != set(warr):
        mism += len(set(garr) ^ set(warr))
    value_names = VALUES[kind]
    gap = 0.0
    for name in warr:
        if name not in garr:
            continue
        g, w = np.asarray(garr[name]), np.asarray(warr[name])
        if g.shape != w.shape:
            mism += 1
            continue
        if name not in value_names:
            mism += int(not np.array_equal(g, w, equal_nan=True))
            continue
        gn, wn = np.isnan(g), np.isnan(w)
        mism += int(np.count_nonzero(gn != wn))
        both = ~(gn | wn)
        if not both.any():
            continue
        d = np.abs(g[both].astype(np.float64) - w[both].astype(np.float64))
        if kind == "qpe":
            d = d / np.maximum(np.abs(w[both].astype(np.float64)),
                               QPE_FLOOR_MM)
        gap = max(gap, float(d.max()))
    return {NUMBER[kind]: gap, "exact_mismatches": float(mism)}


def merge(readings: List[Dict[str, float]]) -> Dict[str, float]:
    """Worst value of each number over several answers (mismatches add)."""
    out: Dict[str, float] = {}
    for r in readings:
        for k, v in r.items():
            if k == "exact_mismatches":
                out[k] = out.get(k, 0.0) + v
            else:
                out[k] = max(out.get(k, 0.0), v)
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}): correct when every number
    is at or under its limit."""
    table = {k: {"value": float(v), "limit": float(limits[k])}
             for k, v in sorted(numbers.items())}
    return all(t["value"] <= t["limit"] for t in table.values()), table


def check_sample(ref: Reference, paths: List[str], bodies: List[bytes],
                 precision: Optional[str] = None,
                 workers: int = 8) -> Dict[str, float]:
    """Compare served bodies (or, with ``precision``, the control in the
    program's place) against the float32 reference."""
    from concurrent.futures import ThreadPoolExecutor

    def one(item):
        path, body = item
        kind, _ = parse(path)
        want = ref.answer(path)
        got = (ref.answer(path, precision) if precision is not None
               else decode_body(body))
        return compare(kind, got, want)

    with ThreadPoolExecutor(max_workers=workers) as pool:
        return merge(list(pool.map(one, zip(paths, bodies))))


__all__ = ["Reference", "check_sample", "compare", "decode_body", "judge",
           "merge", "parse"]
