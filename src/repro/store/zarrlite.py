"""Zarr-like hierarchical array storage over a snapshot manifest.

A *store session* exposes groups and arrays addressed by ``/``-paths.  Array
metadata (shape, dtype, chunk grid, attrs) lives in the snapshot document;
chunk payloads are content-addressed immutable objects.  Reads are lazy and
chunk-granular; writes stage into an open :class:`~repro.store.icechunk.Transaction`.

This module is deliberately storage-format-first: the Radar DataTree layer
(:mod:`repro.core.datatree`) is a *view* over these primitives, exactly as
``xarray.DataTree`` is a view over Zarr in the paper.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from repro import obs

from . import outbuf
from .chunks import (ChunkGrid, normalize_selection, predicate_mask,
                     selection_bounds)
from .codecs import default_codec


@dataclass
class ArrayMeta:
    """Array metadata: shape, dtype, chunk grid, fill and codec."""
    shape: Tuple[int, ...]
    dtype: str
    chunks: Tuple[int, ...]
    attrs: Dict[str, Any] = field(default_factory=dict)
    fill_value: float = float("nan")
    codec: str = field(default_factory=default_codec)

    def to_doc(self) -> Dict[str, Any]:
        return {
            "shape": list(self.shape),
            "dtype": self.dtype,
            "chunks": list(self.chunks),
            "attrs": self.attrs,
            "fill_value": None if np.isnan(self.fill_value) else self.fill_value,
            "codec": self.codec,
        }

    @staticmethod
    def from_doc(doc: Dict[str, Any]) -> "ArrayMeta":
        fv = doc.get("fill_value")
        return ArrayMeta(
            shape=tuple(doc["shape"]),
            dtype=doc["dtype"],
            chunks=tuple(doc["chunks"]),
            attrs=dict(doc.get("attrs", {})),
            fill_value=float("nan") if fv is None else float(fv),
            # snapshots written before codecs were pluggable used zstd
            codec=doc.get("codec", "zstd"),
        )

    @property
    def grid(self) -> ChunkGrid:
        return ChunkGrid(self.shape, self.chunks)


def _chunk_key(cid: Sequence[int]) -> str:
    return "c" + "/".join(str(i) for i in cid) if cid else "c0"


@dataclass
class ScanStats:
    """Chunk accounting for one :meth:`Array.scan` call."""

    n_chunks: int = 0       # candidate chunks examined
    n_pruned: int = 0       # skipped via chunk-statistics sidecars
    n_unwritten: int = 0    # no chunk object exists (fill value only)
    n_read: int = 0         # chunks actually fetched and decoded

    def merge(self, other: "ScanStats") -> None:
        self.n_chunks += other.n_chunks
        self.n_pruned += other.n_pruned
        self.n_unwritten += other.n_unwritten
        self.n_read += other.n_read


@dataclass
class ScanResult:
    """Matches of a predicate scan: global coordinates + values.

    ``coords`` is one int64 index array per axis; ``values`` the matching
    elements.  The ordering (chunks in grid order, row-major within each
    chunk) is deterministic and — because pruning only ever skips chunks
    that cannot contribute a match — identical for every pruning mode.
    """

    coords: Tuple[np.ndarray, ...]
    values: np.ndarray
    stats: ScanStats


def _stats_prune(st, value_gt: Optional[float],
                 value_lt: Optional[float]) -> bool:
    """True when a chunk's ``[min, max, valid]`` triple proves no match."""
    mn, mx, valid = st
    if not valid:  # no valid element at all
        return True
    if value_gt is not None and (mx is None or mx <= value_gt):
        return True
    if value_lt is not None and (mn is None or mn >= value_lt):
        return True
    return False


def _stats_prune_cid(session, path: str, cid, value_gt, value_lt) -> bool:
    """Whether one chunk's stat sidecar proves it cannot match."""
    st = session.chunk_stats(path, cid)
    return st is not None and _stats_prune(st, value_gt, value_lt)


class Array:
    """Lazy chunked array bound to a snapshot (read) or transaction (write)."""

    def __init__(self, session, path: str, meta: ArrayMeta):
        self._session = session
        self.path = path
        self.meta = meta

    # -- reads -------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return self.meta.shape

    @property
    def dtype(self):
        return np.dtype(self.meta.dtype)

    @property
    def chunks(self) -> Tuple[int, ...]:
        """Chunk grid — fixed at creation, rewritten only by the
        compaction maintenance pass (:mod:`repro.store.compaction`)."""
        return self.meta.chunks

    @property
    def attrs(self) -> Dict[str, Any]:
        return self.meta.attrs

    def _normalize_int(self, ax: int, s: int) -> int:
        dim = self.meta.shape[ax]
        if s < 0:
            s += dim
        if not 0 <= s < dim:
            raise IndexError(
                f"index {s} out of bounds for axis {ax} with size {dim}"
            )
        return s

    def __getitem__(self, selection) -> np.ndarray:
        if not isinstance(selection, tuple):
            selection = (selection,)
        # normalize: ints become length-1 slices (squeezed at the end)
        squeeze_axes = []
        sels = []
        for ax, s in enumerate(selection):
            if isinstance(s, (int, np.integer)):
                s = self._normalize_int(ax, int(s))
                sels.append(slice(s, s + 1))
                squeeze_axes.append(ax)
            else:
                sels.append(s)
        while len(sels) < len(self.meta.shape):
            sels.append(slice(None))
        bounds = [sl.indices(dim) for sl, dim in zip(sels, self.meta.shape)]
        out_shape = tuple(max(0, b[1] - b[0]) for b in bounds)
        cids = list(self.meta.grid.chunks_for_selection(sels))
        if len(cids) > 1:
            # the span ``store.read``: a multi-chunk read's wall time on
            # this thread, whose bytes are the output's
            with obs.span("store.read") as sp:
                out = self._assemble(cids, bounds, out_shape)
                sp.nbytes = out.nbytes
        else:
            out = self._assemble(cids, bounds, out_shape)
        if squeeze_axes:
            out = np.squeeze(out, axis=tuple(squeeze_axes))
        return out

    def _assemble(self, cids, bounds, out_shape) -> np.ndarray:
        """The output of a read: the fill value, then each chunk copied
        into its region.  A large output is filled in a block an earlier
        read mapped (:mod:`repro.store.outbuf`).  Several chunks go
        through :meth:`Session.read_chunks`, which may copy some on
        helper threads; each copy is counted as ``store.read.pooled``
        there and ``store.read.inline`` on this thread."""
        # the output buffer and each chunk's copy into it: the span
        # ``store.assemble``, whose bytes are the output's
        with obs.span("store.assemble") as sp:
            out = outbuf.full(out_shape, self.meta.fill_value, self.dtype)
            sp.nbytes = out.nbytes
        grid = self.meta.grid

        def copy(cid) -> None:
            cslices = grid.chunk_slices(cid)
            chunk = self._read_chunk(cid)
            # intersection of chunk extent and request, in both frames
            src, dst = [], []
            for (cs, b) in zip(cslices, bounds):
                lo = max(cs.start, b[0])
                hi = min(cs.stop, b[1])
                src.append(slice(lo - cs.start, hi - cs.start))
                dst.append(slice(lo - b[0], hi - b[0]))
            # destination regions are disjoint per chunk, so copies on
            # several threads never overlap
            with obs.span("store.assemble"):
                out[tuple(dst)] = chunk[tuple(src)]

        if len(cids) <= 1:
            for cid in cids:
                copy(cid)
            return out
        caller = threading.get_ident()

        def fill_from(cid) -> None:
            t0 = time.perf_counter()
            copy(cid)
            obs.record("store.read.inline" if threading.get_ident() == caller
                       else "store.read.pooled", time.perf_counter() - t0)

        self._session.read_chunks(self.path, cids, fill_from)
        return out

    def read(self) -> np.ndarray:
        return self[tuple(slice(None) for _ in self.meta.shape)]

    def scan(
        self,
        selection=None,
        *,
        value_gt: Optional[float] = None,
        value_lt: Optional[float] = None,
        prune: bool = True,
        pushdown: bool = True,
    ) -> ScanResult:
        """Predicate scan with chunk-statistics pushdown.

        A *match* is a valid element (finite, for float dtypes) inside
        ``selection`` satisfying every value predicate.  With ``prune``
        the session's stat sidecars skip chunks that provably cannot
        match; with ``pushdown`` the chunk grid restricts candidates to
        chunks intersecting ``selection`` (when False, every chunk is a
        candidate and the selection is applied as a mask — the "blind
        scan" baseline).  All four mode combinations return bitwise-
        identical coords/values; only :class:`ScanStats` differ.  Multi-
        chunk scans fan out over the session's reader pool when one is
        configured.
        """
        shape = self.meta.shape
        sels = normalize_selection(selection, len(shape))
        bounds = selection_bounds(sels, shape)
        grid = self.meta.grid
        if pushdown:
            cids = list(grid.chunks_for_selection(
                [slice(b0, b1) for b0, b1 in bounds]
            ))
        else:
            cids = list(grid.chunk_ids())
        stats = ScanStats(n_chunks=len(cids))
        session = self._session
        is_float = np.issubdtype(self.dtype, np.floating)
        # only a NaN fill is invalid-by-definition; a finite float fill
        # (create_array allows one) makes unwritten chunks real matches
        fill_invalid = is_float and np.isnan(self.meta.fill_value)

        def scan_chunk(cid):
            if prune:
                st = session.chunk_stats(self.path, cid)
                if st is not None and _stats_prune(st, value_gt, value_lt):
                    return "pruned", None
            unwritten = (
                session.chunk_ref(self.path, cid) is None
                and session.staged_chunk_array(self.path, cid) is None
            )
            # never written: fill value only — a NaN fill is invalid by
            # definition, so nothing can match; any other fill means the
            # (synthesized, not decoded) fill chunk still has to be
            # tested against the predicates
            if unwritten and fill_invalid:
                return "unwritten", None
            chunk = self._read_chunk(cid)
            cslices = grid.chunk_slices(cid)
            mask = predicate_mask(chunk, [cs.start for cs in cslices],
                                  bounds, value_gt, value_lt)
            loc = np.nonzero(mask)
            coords = tuple(
                (l + cs.start).astype(np.int64)
                for l, cs in zip(loc, cslices)
            )
            return ("unwritten" if unwritten else "read"), (coords, chunk[loc])

        pool = session.reader_pool() if len(cids) > 1 else None
        if len(cids) > 1 and not session.writable:
            # batch the manifest + stat-sidecar round trips, then prefetch
            # only the chunks pruning cannot skip — so coalescing changes
            # GET counts, never the gated pruning fetch accounting
            session._prefetch_manifests([self.path], stats=prune)
            if prune:
                survivors = [
                    cid for cid in cids
                    if not _stats_prune_cid(session, self.path, cid,
                                            value_gt, value_lt)
                ]
            else:
                survivors = cids
            session.prefetch([(self.path, survivors)], wait=pool is None)
        if pool is None:
            outcomes = [scan_chunk(cid) for cid in cids]
        else:
            # pool.map preserves submission order, so the concatenation
            # below is deterministic regardless of completion order
            outcomes = list(pool.map(scan_chunk, cids))
        parts = []
        for kind, payload in outcomes:
            if kind == "pruned":
                stats.n_pruned += 1
            else:
                if kind == "unwritten":
                    stats.n_unwritten += 1
                else:
                    stats.n_read += 1
                if payload is not None and payload[1].size:
                    parts.append(payload)
        if parts:
            coords = tuple(
                np.concatenate([p[0][ax] for p in parts])
                for ax in range(len(shape))
            )
            values = np.concatenate([p[1] for p in parts])
        else:
            coords = tuple(
                np.empty(0, dtype=np.int64) for _ in range(len(shape))
            )
            values = np.empty(0, dtype=self.dtype)
        return ScanResult(coords, values, stats)

    def _read_chunk(self, cid) -> np.ndarray:
        """Read one chunk at its *actual* (possibly edge-clipped) extent.

        Chunks are always persisted at the full chunk shape, padded with
        ``fill_value`` at array edges — this keeps appends (resize + write)
        valid without rewriting boundary chunks.
        """
        full = self._read_chunk_padded(cid)
        actual = self.meta.grid.chunk_shape(cid)
        return full[tuple(slice(0, s) for s in actual)]

    def _read_chunk_padded(self, cid, *, writable: bool = False) -> np.ndarray:
        """Full padded chunk.  The default return may be a **read-only**
        array shared via the session's chunk cache; pass ``writable=True``
        to get a private mutable copy (the RMW write path)."""
        staged = self._session.staged_chunk_array(self.path, cid)
        if staged is not None:
            return staged  # already private to this transaction
        chunk = self._session.decoded_chunk(self.path, cid, self.meta)
        if chunk is None:
            return np.full(self.meta.chunks, self.meta.fill_value,
                           dtype=self.dtype)
        return chunk.copy() if writable else chunk

    # -- writes (require an open transaction) ------------------------------
    def __setitem__(self, selection, value) -> None:
        if not isinstance(selection, tuple):
            selection = (selection,)
        sels = list(selection)
        while len(sels) < len(self.meta.shape):
            sels.append(slice(None))
        # normalize ints exactly like __getitem__ — in particular negative
        # indices, which previously produced an empty slice here and made
        # ``arr[-1] = x`` a silent no-op
        norm = []
        for ax, s in enumerate(sels):
            if isinstance(s, (int, np.integer)):
                i = self._normalize_int(ax, int(s))
                norm.append(slice(i, i + 1))
            else:
                norm.append(s)
        sels = norm
        bounds = [sl.indices(dim) for sl, dim in zip(sels, self.meta.shape)]
        value = np.asarray(value, dtype=self.dtype)
        req_shape = tuple(max(0, b[1] - b[0]) for b in bounds)
        value = np.broadcast_to(value, req_shape)
        grid = self.meta.grid
        for cid in grid.chunks_for_selection(sels):
            cslices = grid.chunk_slices(cid)
            src, dst = [], []
            full_cover = True
            for (cs, b, full_c) in zip(cslices, bounds, self.meta.chunks):
                lo = max(cs.start, b[0])
                hi = min(cs.stop, b[1])
                if lo > cs.start or (hi - lo) < full_c:
                    full_cover = False
                dst.append(slice(lo - cs.start, hi - cs.start))
                src.append(slice(lo - b[0], hi - b[0]))
            if full_cover:
                # request covers the whole (full-shape) chunk: no read
                # needed.  Always materialize a private copy — `value` may
                # be (a view of) the caller's buffer or a read-only
                # broadcast, and staged chunks must be caller-isolated and
                # writable for later in-place RMW
                chunk = np.array(value[tuple(src)], dtype=self.dtype,
                                 order="C")
            else:
                # read-modify-write at full padded chunk shape; if the chunk
                # is already staged decoded, this mutates it in place and
                # re-staging is a no-op — repeated appends to the same time
                # chunk pay the codec exactly once, at commit.  writable=True
                # keeps the mutation off the session's shared read cache.
                chunk = self._read_chunk_padded(cid, writable=True)
                chunk[tuple(dst)] = value[tuple(src)]
            self._session.stage_chunk_array(self.path, cid, chunk)

    def write_full(self, value: np.ndarray) -> None:
        self[tuple(slice(None) for _ in self.meta.shape)] = value
