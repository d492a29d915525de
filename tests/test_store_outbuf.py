"""Outputs of large reads in kept host blocks (``repro.store.outbuf``).

Each case reads serially (no pool) and on the process's shared read
pool.  A read's bytes equal the parent's ``np.full``-then-copy output
even in a block that held other data; a block that any array, view or
buffer export still refers to is never handed out again; what the arena
keeps stays within the most it had in use over its horizon.  Most cases
lower the threshold and the block unit so that small arrays take the
arena; one reads a real 36 MiB output.
"""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.store import Repository, outbuf, readpool

SHAPE, CHUNKS = (40, 26, 18), (16, 10, 8)
MODES = ["serial", "shared_pool"]
# the threshold and block unit of the small cases, in bytes
SMALL = 1024


class Clock:
    def __init__(self) -> None:
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    """Arrays with edge chunks and chunks never written, arrays of the
    same shapes holding other values, and the values a read returns."""
    repo = Repository.create(str(tmp_path_factory.mktemp("outbuf") / "r"))
    rng = np.random.default_rng(18)
    x = rng.standard_normal(SHAPE).astype("float32")
    i = rng.integers(-1000, 1000, SHAPE).astype("int16")
    big = rng.standard_normal((64, 32, 32)).astype("float32")
    tx = repo.writable_session()
    tx.create_array("x", shape=SHAPE, dtype="float32", chunks=CHUNKS)[:32] = \
        x[:32]
    tx.create_array("i", shape=SHAPE, dtype="int16", chunks=CHUNKS,
                    fill_value=-7)[:, :20] = i[:, :20]
    tx.create_array("jx", shape=SHAPE, dtype="float32",
                    chunks=CHUNKS).write_full(np.full(SHAPE, 7.5, "float32"))
    tx.create_array("ji", shape=SHAPE, dtype="int16", chunks=CHUNKS,
                    fill_value=-7).write_full(np.full(SHAPE, 99, "int16"))
    tx.create_array("big", shape=big.shape, dtype="float32",
                    chunks=(8, 32, 32)).write_full(big)
    tx.commit("seed")
    x[32:] = np.nan      # the time chunk [32, 48) was never written
    i[:, 20:] = -7       # nor was the column chunk [20, 30)
    return repo, {"x": x, "i": i, "big": big}


@pytest.fixture
def clock(monkeypatch):
    """A fresh arena on an injected clock, for arrays of ``SMALL`` bytes
    or more, in blocks of whole ``SMALL`` bytes."""
    clock = Clock()
    monkeypatch.setattr(outbuf, "_ARENA", outbuf.OutputArena(clock=clock))
    monkeypatch.setattr(outbuf, "MIN_BYTES", SMALL)
    monkeypatch.setattr(outbuf, "_UNIT", SMALL)
    return clock


def _session(repo, mode):
    s = repo.readonly_session()
    if mode != "serial":
        s.read_pool = readpool.shared_pool()
    return s


def _counts():
    table = obs.snapshot()
    return tuple(table.get(name, {"n": 0})["n"]
                 for name in ("store.read.reused", "store.read.fresh"))


def _kept():
    return sum(b.raw.nbytes for b in outbuf._ARENA._blocks)


def _blocks():
    return {id(b.raw) for b in outbuf._ARENA._blocks}


SELECTIONS = {
    "whole": (slice(None),),
    "cut_every_axis": (slice(1, 39), slice(3, 25), slice(2, 17)),
    "int_squeeze": (slice(None), 6, slice(None)),
    "small_int_squeeze": (3, slice(None), slice(1, 8)),
    "two_int_squeezes": (slice(2, 40), 6, 8),
    "negative": (slice(-9, None), slice(None), -1),
    "edge_and_unwritten": (slice(20, 40), slice(11, 26)),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("path", ["x", "i"])
@pytest.mark.parametrize("sel", list(SELECTIONS), ids=list(SELECTIONS))
def test_read_is_bit_identical_in_a_block_that_held_other_data(
        archive, clock, monkeypatch, mode, path, sel):
    repo, want = archive
    selection = SELECTIONS[sel]
    with _session(repo, mode) as s:
        with monkeypatch.context() as m:
            m.setattr(outbuf, "MIN_BYTES", 1 << 62)   # the parent's np.full
            parent = s.array(path)[selection]
        # the same selection of an array of other values fills a block,
        # which is idle once that result is dropped
        s.array("j" + path)[selection]
        before = _counts()
        out = s.array(path)[selection]
    expected = want[path][selection]
    assert out.dtype == parent.dtype == expected.dtype
    assert out.shape == parent.shape == expected.shape
    assert out.tobytes() == parent.tobytes() == expected.tobytes()
    reused = _counts()[0] - before[0]
    assert reused == (1 if expected.nbytes >= SMALL else 0)


@pytest.mark.parametrize("mode", MODES)
def test_unwritten_regions_read_the_fill_value_after_other_data(
        archive, clock, mode):
    repo, want = archive
    with _session(repo, mode) as s:
        s.array("jx")[:]          # 7.5 everywhere, then idle
        before = _counts()
        out = s.array("x")[:]
    assert _counts()[0] == before[0] + 1
    assert np.isnan(out[32:]).all()
    assert out.tobytes() == want["x"].tobytes()


@pytest.mark.parametrize("mode", MODES)
def test_held_results_views_and_exports_keep_their_values(
        archive, clock, mode):
    repo, want = archive
    with _session(repo, mode) as s:
        held = s.array("x")[:]
        view = s.array("x")[:][5:9, 2:20]
        squeezed = s.array("x")[:, 6, :]
        export = memoryview(s.array("i")[:])
        fresh_before = _counts()[1]
        for _ in range(3):
            s.array("jx")[:]
            s.array("jx")[:, 6, :]
            s.array("ji")[:]
    # every later read needed blocks of its own while these were held
    assert _counts()[1] - fresh_before >= 3
    assert held.tobytes() == want["x"].tobytes()
    assert view.tobytes() == want["x"][5:9, 2:20].tobytes()
    assert squeezed.tobytes() == want["x"][:, 6, :].tobytes()
    assert bytes(export) == want["i"].tobytes()


@pytest.mark.parametrize("mode", MODES)
def test_dropping_a_result_reuses_its_block(archive, clock, mode):
    repo, want = archive
    with _session(repo, mode) as s:
        first = s.array("x")[:]
        block = id(first.base)
        del first
        before = _counts()
        second = s.array("x")[:]
    assert _counts() == (before[0] + 1, before[1])
    assert id(second.base) == block
    assert second.tobytes() == want["x"].tobytes()


@pytest.mark.parametrize("mode", MODES)
def test_kept_bytes_stay_within_the_recent_high_water_mark(
        archive, clock, mode):
    """After every read, the arena keeps no more than the most bytes its
    held outputs took at any read within the horizon."""
    repo, want = archive
    rng = np.random.default_rng(7)
    held, in_use = [], []     # outputs held; (time, bytes in use) per read
    with _session(repo, mode) as s:
        for _ in range(60):
            clock.t += float(rng.choice([0.5, 5.0, 20.0, 45.0]))
            lo = int(rng.integers(0, 60))
            hi = int(rng.integers(lo + 1, 65))
            out = s.array("big")[lo:hi]
            assert out.tobytes() == want["big"][lo:hi].tobytes()
            held.append(out)
            in_use.append((clock.t, sum(a.base.nbytes for a in held)))
            high = max(b for t, b in in_use if t >= clock.t - outbuf.HORIZON_S)
            assert _kept() <= high
            # keep up to three outputs, the newest most often
            del out
            while len(held) > 3 or (held and rng.random() < 0.4):
                held.pop(int(rng.integers(0, len(held))))


@pytest.mark.parametrize("mode", MODES)
def test_an_idle_block_past_the_horizon_is_released(archive, clock, mode):
    repo, _want = archive
    with _session(repo, mode) as s:
        a = s.array("big")[:16]       # 64 KiB
        b = s.array("big")[:8]        # 32 KiB: 96 KiB in use
        a_block, b_block = id(a.base), id(b.base)
        del a, b
        clock.t += 30.0
        c = s.array("big")[8:16]      # takes b's block; a's is kept
        assert id(c.base) == b_block
        assert _blocks() == {a_block, b_block}
        del c
        clock.t += outbuf.HORIZON_S + 1.0
        d = s.array("big")[:8]        # a's block served no read since
        assert id(d.base) == b_block
    assert _blocks() == {b_block}


@pytest.mark.parametrize("mode", MODES)
def test_twelve_threads_reading_mixed_sizes(archive, clock, mode):
    """Twelve readers of one session, each holding a few of its outputs
    while the others read: every output keeps its values."""
    repo, want = archive
    errors = []
    switch = sys.getswitchinterval()

    def reader(seed, s) -> None:
        rng = np.random.default_rng(seed)
        kept = []
        try:
            for _ in range(25):
                path = str(rng.choice(["x", "i", "big", "jx"]))
                hi = int(rng.integers(1, s.array(path).shape[0] + 1))
                out = s.array(path)[:hi]
                kept.append((path, hi, out))
                if len(kept) > 2:
                    kept.pop(int(rng.integers(0, len(kept))))
                for p, h, o in kept:
                    ref = (np.full(SHAPE, 7.5, "float32") if p == "jx"
                           else want[p])[:h]
                    assert o.tobytes() == ref.tobytes(), (p, h)
        except BaseException as exc:  # handed to the test below
            errors.append(exc)

    sys.setswitchinterval(1e-5)
    try:
        with _session(repo, mode) as s:
            threads = [threading.Thread(target=reader, args=(n, s))
                       for n in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert not errors, errors[0]


@pytest.mark.parametrize("mode", MODES)
def test_a_read_below_the_threshold_never_touches_the_arena(
        archive, monkeypatch, mode):
    repo, want = archive
    arena = outbuf.OutputArena()
    monkeypatch.setattr(outbuf, "_ARENA", arena)
    before = _counts()
    with _session(repo, mode) as s:
        out = s.array("big")[:]       # 256 KiB, under 32 MiB
    assert out.tobytes() == want["big"].tobytes()
    assert out.base is None
    assert _counts() == before
    assert arena._blocks == []


@pytest.mark.parametrize("mode", MODES)
def test_a_36_mib_read_takes_a_kept_block(tmp_path, monkeypatch, mode):
    """At the real threshold: the first read maps a block, the next one
    of the same size fills it again."""
    monkeypatch.setattr(outbuf, "_ARENA", outbuf.OutputArena())
    shape = (9, 1024, 1024)
    value = np.arange(np.prod(shape), dtype="float32").reshape(shape)
    repo = Repository.create(str(tmp_path / "r"))
    tx = repo.writable_session()
    tx.create_array("v", shape=shape, dtype="float32",
                    chunks=(4, 512, 1024))[:8] = value[:8]
    tx.commit("seed")
    value[8:] = np.nan
    with _session(repo, mode) as s:
        before = _counts()
        first = s.array("v")[:]
        assert _counts() == (before[0], before[1] + 1)
        assert first.nbytes >= outbuf.MIN_BYTES
        assert first.tobytes() == value.tobytes()
        first[:] = 1.0            # the next read must not see this
        del first
        second = s.array("v")[:]
        assert _counts() == (before[0] + 1, before[1] + 1)
    assert second.tobytes() == value.tobytes()


@pytest.mark.parametrize("mode", MODES)
def test_an_output_jax_holds_keeps_its_values(archive, clock, mode):
    """An output handed to JAX and then dropped by the caller: whatever
    JAX keeps of it reads the same after later reads of the same size."""
    import jax

    repo, want = archive
    with _session(repo, mode) as s:
        on_device = jax.device_put(s.array("x")[:])
        for _ in range(3):
            s.array("jx")[:]
    assert np.asarray(on_device).tobytes() == want["x"].tobytes()
