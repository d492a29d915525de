"""Static↔dynamic lockset agreement report.

The static ``lock-discipline`` pass *infers* guards ("``Session._own_pool``
is guarded by ``Session._cache_lock``"); the dynamic sanitizer *observes*
locksets (the intersection of locks actually held across every traced
access to the attribute).  This module joins the two over
``src/repro/store`` and ``src/repro/serve``: every guard the static
pass infers must be **confirmed** by the dynamic run —

* ``confirmed`` — the attribute was exercised and the inferred lock was
  held on every access,
* ``refuted`` — the attribute was exercised but some access did not hold
  the inferred lock: either the static inference or the runtime locking
  is wrong, and the build fails,
* ``unobserved`` — the workload never touched the attribute: the
  cross-check is vacuous, which also fails the build (the workload must
  keep pace with the instrumentation).

Any data race detected during the workload fails the report too.  Run it
via ``scripts/lint.py --dynamic``.
"""

from __future__ import annotations

import shutil
import tempfile
import threading
from typing import Any, Dict

import numpy as np

from .runtime import rt

# agreement scope: the transactional store (where both the static pass
# and the instrumentation are densest) plus the serve layer's scheduling
# substrate and service state (PR 8)
_SCOPE = ("src/repro/store", "src/repro/serve")


def _exercise_store() -> None:
    """Drive every Session surface whose guard the static pass infers:
    pool build (``_own_pool``), manifest/stat object cache
    (``_obj_cache``), chunk cache + byte budget + fetch counter
    (``_chunk_cache`` / ``_chunk_cache_nbytes`` / ``_fetch_count``),
    the prefetch pipeline (``_inflight`` / ``_prefetch_hot`` /
    ``_prefetch_hits``), the simulated-latency backend's counters,
    ``cache_stats`` reads, and ``close`` — including two concurrent
    readers so the locksets are observed under real contention.  Their
    32 MiB reads take the output arena (``_blocks`` / ``_in_use``): one
    made here, since the process's own was made with tracing off."""
    from repro.store import (ObjectStore, Repository, SimulatedLatencyStore,
                             outbuf)

    root = tempfile.mkdtemp(prefix="repro-tsan-agree-")
    try:
        repo = Repository.create(f"{root}/repo")
        tx = repo.writable_session()
        tx.create_array("x", shape=(8,), dtype="float32",
                        chunks=(4,)).write_full(np.arange(8, dtype="float32"))
        # the smallest output the arena takes, in two chunks
        big = (2, outbuf.MIN_BYTES // 8)
        tx.create_array("big", shape=big, dtype="float32",
                        chunks=(1, big[1])).write_full(np.ones(big, "float32"))
        tx.commit("seed")

        # reopen over the simulated-latency backend (sleepless) so its
        # request counters and the prefetch pipeline are both observed
        sim = SimulatedLatencyStore(ObjectStore(f"{root}/repo"), sleep=False)
        s = Repository.open(sim).readonly_session(read_workers=2)
        arena, outbuf._ARENA = outbuf._ARENA, outbuf.OutputArena()
        try:
            s.reader_pool()
            s.prefetch(["x"], wait=True)    # _inflight / _prefetch_hot

            def read() -> None:
                np.testing.assert_array_equal(
                    s.array("x").read(), np.arange(8, dtype="float32"))
                assert (s.array("big").read() == 1).all()

            threads = [threading.Thread(target=read, name=f"agree-r{i}")
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            s.array("x").read()     # warm-cache hit path
            s.array("big").read()   # an idle block filled again
            s.cache_stats()         # includes prefetch-hit counters
            sim.stats()
            sim.reset_stats()
        finally:
            s.close()
            outbuf._ARENA = arena
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _exercise_serve() -> None:
    """Drive every serve surface whose guard the static pass infers:
    ``SingleFlight``'s coalescing map and counters (two concurrent
    requests on one key), ``ByteBudgetCache``'s entries/bytes/hit
    counters (hit, miss, eviction, drain), and the archive service's
    per-tenant session table."""
    from repro.serve.http import ArchiveService
    from repro.serve.scheduling import ByteBudgetCache, SingleFlight

    flight = SingleFlight()
    barrier = threading.Barrier(2)

    def request() -> None:
        barrier.wait()
        flight.do("product:qvp", lambda: b"payload")

    threads = [threading.Thread(target=request, name=f"agree-sf{i}")
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    flight.stats()

    cache = ByteBudgetCache(8)
    cache.put("a", b"aaaa", 4)
    cache.get("a")              # hit
    cache.get("missing")        # miss
    cache.put("b", b"bbbbbb", 6)  # evicts "a" (byte budget)
    cache.stats()
    cache.pop_all()

    service = ArchiveService(catalog=None)
    service._sessions_for("tenant-a")
    service.stats()
    service.close()


def agreement_report(repo_root: str = ".") -> Dict[str, Any]:
    """Run the static inference and the dynamic workload; join them.

    Returns ``{"scope", "guards": {name: {static_locks, status,
    observed_lockset, accesses}}, "races_during_workload", "ok"}`` —
    ``ok`` only when every static guard is confirmed and the workload
    was race-free.
    """
    from repro.analysis.checkers.lock_discipline import inferred_guards
    from repro.analysis.core import Project

    static = {
        key: info
        for key, info in inferred_guards(Project(repo_root)).items()
        if str(info["module"]).startswith(_SCOPE)
    }

    with rt.scoped() as scope:
        _exercise_store()
        _exercise_serve()
        det = scope.detector
        observed = {
            key: {
                "lockset": sorted(o["lockset"] or ()),
                "accesses": o["accesses"],
                "writes": o["writes"],
            }
            for key, o in det.observations.items()
        }
        races = [r.to_doc() for r in det.races]

    guards: Dict[str, Any] = {}
    ok = not races
    for key, info in sorted(static.items()):
        obs = observed.get(key)
        if obs is None or obs["accesses"] == 0:
            status = "unobserved"
        elif set(info["locks"]) <= set(obs["lockset"]):
            status = "confirmed"
        else:
            status = "refuted"
        if status != "confirmed":
            ok = False
        guards[key] = {
            "static_locks": list(info["locks"]),
            "status": status,
            "observed_lockset": obs["lockset"] if obs else [],
            "accesses": obs["accesses"] if obs else 0,
        }
    if not guards:
        ok = False      # static pass inferring nothing is itself a bug

    return {
        "scope": _SCOPE,
        "guards": guards,
        "observed": observed,
        "races_during_workload": races,
        "ok": ok,
    }


__all__ = ["agreement_report"]
