"""Live scenario corpus: the real concurrent surfaces under exploration.

Unlike :mod:`.seeded` (deliberately buggy miniatures), every scenario
here drives the *actual* `repro.store` / `repro.catalog` code and is
expected to survive **every** explored interleaving — a defect on any
schedule is a real bug in the live tree.  The corpus covers the
concurrent entry points the ROADMAP's service ambitions lean on:

* ``commit-vs-commit-rebase`` — two transactions on disjoint arrays race
  the branch-ref CAS; the loser must rebase and both commits land,
* ``gc-vs-inflight-commit`` — a gc sweep races a staging+committing
  transaction; the write-ahead grace window must protect the in-flight
  objects,
* ``compact-vs-append`` — compaction replans on top of a concurrent
  append and neither side's data is lost,
* ``close-vs-first-read`` — ``Session.close()`` races the first
  ``reader_pool()`` build (the PR 6 fix, now on the live code),
* ``catalog-register-cas-retry`` — two ``register_repository`` calls
  merge through the catalog document's read-modify-CAS loop,
* ``feed-vs-compaction`` — a scan-per-commit :class:`repro.etl.LiveFeed`
  races background compaction on the same repository; the compactor
  rebases over the appends and no scan is lost or torn,
* ``pooled-reads`` — two multi-chunk reads on one session share its
  reader pool (:mod:`repro.store.readpool`): each caller takes chunks
  beside its helpers and waits only for helpers holding one.

``scripts/lint.py --dynamic`` sweeps this corpus with
:func:`repro.analysis.dynamic.scheduler.verify_clean`; regression tests
replay individual scenarios.
"""

from __future__ import annotations

import shutil
import tempfile
from typing import Callable, Dict, List, Optional

import numpy as np

from .scheduler import RunResult, Scenario, verify_clean


def _mkdtemp() -> str:
    return tempfile.mkdtemp(prefix="repro-tsan-live-")


def _teardown(ctx) -> None:
    shutil.rmtree(ctx["root"], ignore_errors=True)


def _new_repo(root: str):
    from repro.store import Repository

    return Repository.create(f"{root}/repo")


def commit_vs_commit_rebase() -> Scenario:
    """Two writers commit disjoint arrays; the CAS loser rebases."""

    def setup():
        root = _mkdtemp()
        repo = _new_repo(root)
        tx = repo.writable_session()
        tx.create_array("base", shape=(4,), dtype="int32",
                        chunks=(4,)).write_full(np.arange(4, dtype="int32"))
        tx.commit("seed")
        return {"root": root, "repo": repo}

    def writer(name: str):
        def body(ctx) -> None:
            tx = ctx["repo"].writable_session()
            tx.create_array(name, shape=(4,), dtype="int32",
                            chunks=(4,)).write_full(
                np.full(4, ord(name[0]), dtype="int32"))
            tx.commit(f"add {name}")

        return body

    def check(ctx) -> None:
        s = ctx["repo"].readonly_session()
        for name in ("base", "x", "y"):
            assert s.has_array(name), f"lost commit: array {name!r} missing"
        np.testing.assert_array_equal(
            s.array("x").read(), np.full(4, ord("x"), dtype="int32"))
        np.testing.assert_array_equal(
            s.array("y").read(), np.full(4, ord("y"), dtype="int32"))

    return Scenario("commit-vs-commit-rebase", setup,
                    [("writer-x", writer("x")), ("writer-y", writer("y"))],
                    check=check, teardown=_teardown)


def gc_vs_inflight_commit() -> Scenario:
    """A gc sweep races a commit; write-ahead objects must survive."""

    def setup():
        root = _mkdtemp()
        repo = _new_repo(root)
        tx = repo.writable_session()
        tx.create_array("a", shape=(4,), dtype="int32",
                        chunks=(2,)).write_full(np.arange(4, dtype="int32"))
        tx.commit("seed")
        # superseding commit leaves snapshot-1-only objects for gc to weigh
        tx2 = repo.writable_session()
        tx2.array("a").write_full(np.arange(10, 14, dtype="int32"))
        tx2.commit("supersede")
        return {"root": root, "repo": repo}

    def committer(ctx) -> None:
        tx = ctx["repo"].writable_session()
        tx.create_array("b", shape=(4,), dtype="int32",
                        chunks=(2,)).write_full(np.arange(4, dtype="int32"))
        tx.commit("inflight")

    def sweeper(ctx) -> None:
        # default grace window: in-flight write-ahead objects are young
        # and must be kept even though they are not referenced yet
        ctx["repo"].gc()

    def check(ctx) -> None:
        s = ctx["repo"].readonly_session()
        np.testing.assert_array_equal(
            s.array("a").read(), np.arange(10, 14, dtype="int32"))
        np.testing.assert_array_equal(
            s.array("b").read(), np.arange(4, dtype="int32"))

    return Scenario("gc-vs-inflight-commit", setup,
                    [("committer", committer), ("sweeper", sweeper)],
                    check=check, teardown=_teardown)


def compact_vs_append() -> Scenario:
    """Compaction replans on top of a concurrent append (PR 4 semantics:
    a CAS conflict means replan on the winner, never drop either side)."""

    def setup():
        root = _mkdtemp()
        repo = _new_repo(root)
        # append-fragmented layout: 4 commits of 1 row each
        tx = repo.writable_session()
        tx.create_array("t", shape=(4, 4), dtype="float32", chunks=(1, 4))
        tx.commit("schema")
        for i in range(4):
            tx = repo.writable_session()
            tx.array("t")[i] = np.full(4, float(i), dtype="float32")
            tx.commit(f"append {i}")
        return {"root": root, "repo": repo}

    def compactor(ctx) -> None:
        ctx["repo"].compact("timeseries")

    def appender(ctx) -> None:
        tx = ctx["repo"].writable_session()
        tx.create_array("u", shape=(2,), dtype="int32",
                        chunks=(2,)).write_full(np.arange(2, dtype="int32"))
        tx.commit("concurrent append")

    def check(ctx) -> None:
        s = ctx["repo"].readonly_session()
        expect = np.stack([np.full(4, float(i), dtype="float32")
                           for i in range(4)])
        np.testing.assert_array_equal(s.array("t").read(), expect)
        np.testing.assert_array_equal(
            s.array("u").read(), np.arange(2, dtype="int32"))

    return Scenario("compact-vs-append", setup,
                    [("compactor", compactor), ("appender", appender)],
                    check=check, teardown=_teardown)


def close_vs_first_read() -> Scenario:
    """``Session.close()`` races the first reader-pool build.

    The live
    code's locked pool swap must leave no unordered access (the pre-fix
    shape of this is the ``session-close-pool-leak`` seeded case)."""

    def setup():
        root = _mkdtemp()
        repo = _new_repo(root)
        tx = repo.writable_session()
        tx.create_array("x", shape=(4,), dtype="int32",
                        chunks=(2,)).write_full(np.arange(4, dtype="int32"))
        tx.commit("seed")
        return {"root": root,
                "session": repo.readonly_session(read_workers=2)}

    def reader(ctx) -> None:
        ctx["session"].reader_pool()

    def closer(ctx) -> None:
        ctx["session"].close()

    def final_close(ctx) -> None:
        ctx["session"].close()
        _teardown(ctx)

    return Scenario("close-vs-first-read", setup,
                    [("reader", reader), ("closer", closer)],
                    teardown=final_close)


def catalog_register_cas_retry() -> Scenario:
    """Two ``register_repository`` upserts race through the CAS loop.

    Both merge through the catalog document compare-and-swap; neither
    registration may be lost."""

    def setup():
        from repro.catalog import Catalog

        root = _mkdtemp()
        repo = _new_repo(root)
        tx = repo.writable_session()
        tx.create_group("", {"site_id": "KTST", "latitude": 35.0,
                             "longitude": -97.0, "altitude": 300.0})
        tx.create_group("vcp_11", {"vcp_id": 11})
        tx.create_array("vcp_11/time", shape=(3,), dtype="float64",
                        chunks=(3,)).write_full(
            np.array([0.0, 60.0, 120.0]))
        tx.commit("tiny site")
        catalog = Catalog.create(f"{root}/catalog")
        return {"root": root, "repo": repo, "catalog": catalog}

    def register(rid: str):
        def body(ctx) -> None:
            ctx["catalog"].register_repository(ctx["repo"], repo_id=rid)

        return body

    def check(ctx) -> None:
        ids = ctx["catalog"].repository_ids()
        assert ids == ["site-a", "site-b"], (
            f"lost registration: expected both entries, got {ids}"
        )
        head = ctx["repo"].branch_head("main")
        for rid in ids:
            entry = ctx["catalog"].entry(rid)
            assert entry.snapshot_id == head, (
                f"{rid}: stale snapshot {entry.snapshot_id!r} != {head!r}"
            )

    return Scenario("catalog-register-cas-retry", setup,
                    [("register-a", register("site-a")),
                     ("register-b", register("site-b"))],
                    check=check, teardown=_teardown)


def feed_vs_compaction() -> Scenario:
    """A live scan-per-commit feed races background compaction.

    The streaming-ingest upkeep interleaving: the compactor's CAS loop
    must replan over whatever the feed committed meanwhile, the feed's
    append must rebase over a landed compaction, and every scan must
    survive re-chunking bit for bit."""

    def setup():
        from repro.etl import LiveFeed, live_scan_feed

        root = _mkdtemp()
        repo = _new_repo(root)
        feed = LiveFeed(repo, live_scan_feed(n_az=8, n_gates=12,
                                             n_sweeps=1))
        feed.ingest_next(2)   # fragmented baseline worth compacting
        return {"root": root, "repo": repo, "feed": feed}

    def feeder(ctx) -> None:
        ctx["feed"].ingest_next(1)

    def compactor(ctx) -> None:
        ctx["repo"].compact("timeseries")

    def check(ctx) -> None:
        assert ctx["feed"].report.n_commits == 3
        s = ctx["repo"].readonly_session()
        t = s.array("VCP-212/time").read()
        assert t.shape == (3,), f"lost scan: time axis {t.shape}"
        assert np.all(np.diff(t) > 0), f"non-monotone time {t}"
        dbz = s.array("VCP-212/sweep_0/DBZH").read()
        assert dbz.shape[0] == 3 and np.isfinite(dbz).any()

    return Scenario("feed-vs-compaction", setup,
                    [("feeder", feeder), ("compactor", compactor)],
                    check=check, teardown=_teardown)


def pooled_reads() -> Scenario:
    """Two readers of one session spread their chunks over its pool."""

    def setup():
        root = _mkdtemp()
        repo = _new_repo(root)
        tx = repo.writable_session()
        tx.create_array("x", shape=(8,), dtype="int32",
                        chunks=(2,)).write_full(np.arange(8, dtype="int32"))
        tx.commit("seed")
        return {"root": root,
                "session": repo.readonly_session(read_workers=2)}

    def reader(ctx) -> None:
        np.testing.assert_array_equal(ctx["session"].array("x").read(),
                                      np.arange(8, dtype="int32"))

    def final_close(ctx) -> None:
        ctx["session"].close()
        _teardown(ctx)

    return Scenario("pooled-reads", setup,
                    [("r0", reader), ("r1", reader)], teardown=final_close)


CORPUS: Dict[str, Callable[[], Scenario]] = {
    "commit-vs-commit-rebase": commit_vs_commit_rebase,
    "gc-vs-inflight-commit": gc_vs_inflight_commit,
    "compact-vs-append": compact_vs_append,
    "close-vs-first-read": close_vs_first_read,
    "catalog-register-cas-retry": catalog_register_cas_retry,
    "feed-vs-compaction": feed_vs_compaction,
    "pooled-reads": pooled_reads,
}


def sweep(names: Optional[List[str]] = None, *, depth: int = 6,
          max_schedules: int = 24) -> Dict[str, Optional[RunResult]]:
    """Explore each live scenario under the schedule explorer.

    A non-None value is a real defect in
    the live tree (its ``schedule`` replays it)."""
    out: Dict[str, Optional[RunResult]] = {}
    for name in (names or sorted(CORPUS)):
        out[name] = verify_clean(CORPUS[name], depth=depth,
                                 max_schedules=max_schedules)
    return out


__all__ = ["CORPUS", "sweep"] + list(CORPUS)
