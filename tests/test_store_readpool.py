"""Multi-chunk reads spread over a host pool (``repro.store.readpool``).

Each case runs a read serially (no pool) and on a pool lent to the
session, the way ``ArchiveService`` lends the process's shared pool: the
output is bit-identical, every chunk is fetched and decoded once, a
failure reaches the caller and lets go of every prefetch hold, and no
read waits on work queued behind it in the pool.
"""

from __future__ import annotations

import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro import obs
from repro.store import Repository, readpool
from repro.store import icechunk

SHAPE, CHUNKS = (10, 13, 9), (4, 5, 4)
MODES = ["serial", "shared_pool"]
# (cache_bytes id, bytes): a cache that admits the read's prefetch plan,
# and one that defers every chunk to demand reads
CACHES = [("prefetched", 1 << 30), ("demand", 1)]


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    """Two arrays with edge chunks and chunks never written; the values
    a read must return."""
    repo = Repository.create(str(tmp_path_factory.mktemp("readpool") / "r"))
    rng = np.random.default_rng(11)
    x = rng.standard_normal(SHAPE).astype("float32")
    i = rng.integers(-1000, 1000, SHAPE).astype("int16")
    tx = repo.writable_session()
    tx.create_array("x", shape=SHAPE, dtype="float32", chunks=CHUNKS)[:8] = \
        x[:8]
    tx.create_array("i", shape=SHAPE, dtype="int16", chunks=CHUNKS,
                    fill_value=-7)[:, :10] = i[:, :10]
    tx.commit("seed")
    x[8:] = np.nan      # the time chunk [8, 12) was never written
    i[:, 10:] = -7      # nor was the column chunk [10, 15)
    return repo, {"x": x, "i": i}


def _session(repo, mode, pool=None, **kw):
    s = repo.readonly_session(**kw)
    if mode != "serial":
        s.read_pool = pool or readpool.shared_pool()
    return s


SELECTIONS = {
    "whole": (slice(None),),
    "cut_every_axis": (slice(1, 9), slice(3, 12), slice(2, 9)),
    "int_squeeze": (3, slice(None), slice(1, 8)),
    "two_int_squeezes": (slice(2, 10), 6, 8),
    "negative": (slice(-3, None), slice(None), -1),
    "edge_and_unwritten": (slice(5, 10), slice(11, 13)),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("path", ["x", "i"])
@pytest.mark.parametrize("sel", list(SELECTIONS), ids=list(SELECTIONS))
def test_read_is_bit_identical(archive, mode, path, sel):
    repo, want = archive
    with _session(repo, "serial") as s:
        serial = s.array(path)[SELECTIONS[sel]]
    with _session(repo, mode) as s:
        out = s.array(path)[SELECTIONS[sel]]
    expected = want[path][SELECTIONS[sel]]
    assert out.dtype == serial.dtype == expected.dtype
    assert out.shape == serial.shape == expected.shape
    assert out.tobytes() == serial.tobytes() == expected.tobytes()


@pytest.mark.parametrize("cache", [c for c, _b in CACHES])
def test_chunk_fetches_are_equal_in_both_modes(archive, cache):
    repo, _want = archive
    fetches = {}
    for mode in MODES:
        with _session(repo, mode, cache_bytes=dict(CACHES)[cache]) as s:
            s.array("x")[:]
            s.array("i")[1:9, 2:13]
            stats = s.cache_stats()
        assert stats["prefetch_inflight"] == 0
        fetches[mode] = stats["chunk_fetches"]
    # x: 2 written time rows x 3 x 3; i: 3 x 2 written column chunks x 3
    assert fetches["serial"] == fetches["shared_pool"] == 18 + 18


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cache", [c for c, _b in CACHES])
def test_a_failing_chunk_reaches_the_caller_and_releases_holds(
        archive, monkeypatch, mode, cache):
    repo, _want = archive
    with _session(repo, mode, cache_bytes=dict(CACHES)[cache]) as s:
        bad = s.chunk_ref("x", (1, 1, 1))
        decode = icechunk.decode_chunk

        def failing(blob, *args, **kw):
            if icechunk.content_hash(blob) == bad:
                raise ValueError("corrupt chunk")
            return decode(blob, *args, **kw)

        monkeypatch.setattr(icechunk, "decode_chunk", failing)
        with pytest.raises(ValueError, match="corrupt chunk"):
            s.array("x")[:]
        assert s.cache_stats()["prefetch_inflight"] == 0
        monkeypatch.setattr(icechunk, "decode_chunk", decode)
        # the session still reads once the chunk decodes again
        np.testing.assert_array_equal(s.array("x")[:4, 2:8],
                                      archive[1]["x"][:4, 2:8])


def _blocked(pool, n):
    """Occupy ``n`` of ``pool``'s workers until the returned event is
    set."""
    started, release = threading.Barrier(n + 1), threading.Event()

    def hold():
        started.wait(timeout=10)
        release.wait(timeout=30)

    for _ in range(n):
        pool.submit(hold)
    started.wait(timeout=10)
    return release


def test_concurrent_reads_finish_on_one_free_worker(archive):
    """Two reads on a pool with one free worker: one read's helper queues
    behind the other's, and neither waits for queued work."""
    repo, want = archive
    pool = ThreadPoolExecutor(max_workers=2)
    release = _blocked(pool, 1)
    try:
        outs, errors = {}, []

        def read(path):
            try:
                with _session(repo, "shared_pool", pool=pool,
                              cache_bytes=1) as s:
                    outs[path] = s.array(path)[:]
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        threads = [threading.Thread(target=read, args=(p,))
                   for p in ("x", "i")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        for path in ("x", "i"):
            assert outs[path].tobytes() == want[path].tobytes()
    finally:
        release.set()
        pool.shutdown(wait=True)


def test_a_read_on_a_fully_taken_pool_finishes_on_its_caller(archive):
    repo, want = archive
    pool = ThreadPoolExecutor(max_workers=2)
    release = _blocked(pool, 2)
    try:
        before = obs.snapshot()
        with _session(repo, "shared_pool", pool=pool) as s:
            out = s.array("x")[:]
        after = obs.snapshot()
        assert out.tobytes() == want["x"].tobytes()
        n = {k: after.get(k, {"n": 0})["n"] - before.get(k, {"n": 0})["n"]
             for k in ("store.read.inline", "store.read.pooled")}
        assert n == {"store.read.inline": 27, "store.read.pooled": 0}
    finally:
        release.set()
        pool.shutdown(wait=True)


def test_a_demand_read_takes_over_a_queued_prefetch(archive):
    """A prefetch whose batches sit queued behind a busy pool does not
    hold up the read of the same chunks: the read fetches them itself,
    once each, and the batches find nothing left to do."""
    repo, want = archive
    pool = ThreadPoolExecutor(max_workers=1)
    release = _blocked(pool, 1)
    try:
        with _session(repo, "shared_pool", pool=pool) as s:
            report = s.prefetch(["x"], wait=False)
            assert report.scheduled == 18
            out = s.array("x")[:]
            assert s.cache_stats()["chunk_fetches"] == 18
            release.set()
            report.wait()
            stats = s.cache_stats()
        assert out.tobytes() == want["x"].tobytes()
        assert stats["chunk_fetches"] == 18
        assert stats["prefetch_inflight"] == 0
    finally:
        release.set()
        pool.shutdown(wait=True)


class _CountingPool(ThreadPoolExecutor):
    """An executor that records how many chunks each of its tasks
    decoded."""

    def __init__(self, max_workers):
        super().__init__(max_workers=max_workers)
        self.decodes = []
        self.local = threading.local()

    def submit(self, fn, *args, **kw):
        def task():
            self.local.n = 0
            try:
                return fn(*args, **kw)
            finally:
                self.decodes.append(self.local.n)
        return super().submit(task)


def test_a_background_prefetch_decodes_one_chunk_per_pool_task(
        archive, monkeypatch):
    """A batch task only GETs; each chunk's decode is a pool task of its
    own, so no read waits on a batch decoding chunks it does not need."""
    repo, _want = archive
    pool = _CountingPool(max_workers=4)
    decode = icechunk.decode_chunk

    def counted(*args, **kw):
        if hasattr(pool.local, "n"):
            pool.local.n += 1
        return decode(*args, **kw)

    monkeypatch.setattr(icechunk, "decode_chunk", counted)
    try:
        with _session(repo, "shared_pool", pool=pool) as s:
            report = s.prefetch(["x"], wait=False).wait()
            assert s.cache_stats()["chunk_fetches"] == 18
    finally:
        pool.shutdown(wait=True)
    assert report.batches == 2
    assert len(pool.decodes) == 2 + 18
    assert sorted(pool.decodes) == [0, 0] + [1] * 18


def test_spans_and_counters(archive, monkeypatch):
    """``store.read`` once per multi-chunk read, with the output's
    bytes; one pooled or inline count per chunk; none for a one-chunk
    read.  A decode that waits for a second thread proves the pool ran a
    chunk beside the caller."""
    repo, want = archive
    decode = icechunk.decode_chunk
    seen, both = set(), threading.Event()
    lock = threading.Lock()

    def paired(*args, **kw):
        with lock:
            seen.add(threading.get_ident())
            if len(seen) >= 2:
                both.set()
        assert both.wait(timeout=10), "no second thread decoded"
        return decode(*args, **kw)

    def window(mode, sel):
        before = obs.snapshot()
        with ThreadPoolExecutor(4) as pool, \
                _session(repo, mode, pool=pool, cache_bytes=1) as s:
            out = s.array("x")[sel]
        after = obs.snapshot()
        delta = {k: {f: after[k][f] - before.get(k, {f: 0})[f]
                     for f in ("n", "bytes")}
                 for k in ("store.read", "store.read.inline",
                           "store.read.pooled") if k in after}
        return out, {k: v for k, v in delta.items() if v["n"]}

    out, d = window("serial", (slice(None),))
    assert d["store.read"] == {"n": 1, "bytes": out.nbytes}
    assert d["store.read.inline"]["n"] == 27
    assert "store.read.pooled" not in d

    monkeypatch.setattr(icechunk, "decode_chunk", paired)
    out, d = window("shared_pool", (slice(None),))
    assert out.tobytes() == want["x"].tobytes()
    assert d["store.read"] == {"n": 1, "bytes": out.nbytes}
    # helpers start first, so the caller may find every chunk taken
    inline = d.get("store.read.inline", {"n": 0})["n"]
    assert d["store.read.pooled"]["n"] >= 1
    assert inline + d["store.read.pooled"]["n"] == 27

    _out, d = window("shared_pool", (slice(0, 4), slice(0, 5), 1))
    assert d == {}


def test_concurrent_reads_stress(archive):
    """Many readers (more than cores) on one session with a one-chunk
    cache and a lent pool, switching threads as often as possible: every
    output is right and no prefetch hold is left behind."""
    repo, want = archive
    pool = ThreadPoolExecutor(max_workers=8)
    session = _session(repo, "shared_pool", pool=pool, cache_bytes=1024)
    sels = list(SELECTIONS.values())
    errors = []

    def work(k):
        try:
            for j in range(6):
                path = "xi"[(k + j) % 2]
                sel = sels[(k * 7 + j) % len(sels)]
                session.prefetch([(path, sel)], wait=(j % 3 == 0))
                out = session.array(path)[sel]
                if out.tobytes() != want[path][sel].tobytes():
                    errors.append((path, sel))
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
        pool.shutdown(wait=True)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert session.cache_stats()["prefetch_inflight"] == 0
    session.close()


@pytest.mark.parametrize("read_workers", [None, 3])
def test_the_service_lends_the_shared_pool_unless_sized(archive, tmp_path,
                                                        read_workers):
    """``ArchiveService`` lends the shared pool to its sessions and to a
    mosaic's (``federation._fan_out``), unless ``read_workers`` sizes
    the sessions' own pools."""
    from repro.catalog import Catalog, federation
    from repro.serve.http import ArchiveService

    repo, _want = archive
    catalog = Catalog.create(str(tmp_path / "catalog"))
    catalog.register_repository(repo, repo_id="R")
    service = ArchiveService(catalog, read_workers=read_workers)
    try:
        session = service.session("tenant", "R")
        lent = readpool.shared_pool() if read_workers is None else None
        assert session.read_pool is lent
        assert session.read_workers == (read_workers or 1)
        got = federation._fan_out(
            catalog, {"R": None}, lambda s, _p: s.read_pool, workers=1,
            read_workers=service._read_workers, read_pool=service._read_pool)
        assert got["R"] is lent
    finally:
        service.close()
