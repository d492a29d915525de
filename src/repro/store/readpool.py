"""One read's chunks, spread over a host thread pool.

A read that covers several chunks makes one work list of its tasks (the
batched GETs of its prefetch plan, then one task per chunk: decode it
and copy it into its own region of the output).  The calling thread
takes tasks from the list itself, and helper tasks on the pool, one
fewer than the pool has threads, take from it too.  A helper that
starts after the list is empty returns at once, and the caller waits
only for helpers that already hold a task.  So a read is never slower
than the caller alone, and a long read's helpers queued in a shared
pool never hold up a short read behind them: its caller does its work.

zstd's decompress and numpy's copies release the interpreter lock, so
threads are enough; no process pool is needed.

:func:`shared_pool` is the process's one pool for reads, sized from the
host's core count, that :class:`~repro.serve.http.ArchiveService` lends
to every session it opens.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable

from repro.analysis.dynamic.runtime import (external_wait, new_lock,
                                            note_read, note_write)

# the shared pool's threads: one per core, at most 16 (on a 13-core TPU
# v5e host a 24 h read ran no faster on 16 threads than on 13)
_SHARED_WIDTH = min(os.cpu_count() or 1, 16)
# no thread starts until the first task is submitted
_SHARED = ThreadPoolExecutor(max_workers=_SHARED_WIDTH,
                             thread_name_prefix="repro-shared-read")


def shared_pool() -> ThreadPoolExecutor:
    """The process's shared read pool (never shut down)."""
    return _SHARED


def _width(pool) -> int:
    """How many threads ``pool`` runs at once (1 when it does not say)."""
    return max(1, int(getattr(pool, "_max_workers", 1)))


class _WorkList:
    """One read's tasks, taken in order by its caller and its helpers."""

    def __init__(self, tasks: Iterable[Callable[[], object]]) -> None:
        self._lock = new_lock("_WorkList._lock")
        self._tasks = deque(tasks)
        self._active = 0        # tasks being run right now
        self._error = None      # the first task's failure
        # set once the list is empty and no task runs; after the list
        # empties no task can start, so it is never cleared
        self._quiet = threading.Event()
        if not self._tasks:
            self._quiet.set()

    def work(self) -> None:
        """Run tasks until the list is empty; a failure empties it."""
        while True:
            with self._lock:
                note_write(self, "_tasks", owner="_WorkList")
                if not self._tasks:
                    return
                task = self._tasks.popleft()
                note_write(self, "_active", owner="_WorkList")
                self._active += 1
            try:
                task()
            except BaseException as exc:  # handed to the caller in drain()
                with self._lock:
                    note_write(self, "_error", owner="_WorkList")
                    if self._error is None:
                        self._error = exc
                    note_write(self, "_tasks", owner="_WorkList")
                    self._tasks.clear()
            finally:
                # let go of the task, and of the read's output it holds,
                # before the caller can learn that the list is done
                task = None
                with self._lock:
                    note_write(self, "_active", owner="_WorkList")
                    self._active -= 1
                    if not self._active and not self._tasks:
                        self._quiet.set()

    def error(self):
        with self._lock:
            note_read(self, "_error", owner="_WorkList")
            return self._error


def drain(pool, tasks) -> None:
    """Run each of ``tasks`` once, here and on helpers on ``pool``.

    Helpers, one fewer than ``pool`` has threads, take tasks beside this
    thread; it returns when every task has run.  The first task to raise
    empties the list; the exception is raised here once the tasks
    already running have finished.  Tasks must be safe to run
    concurrently.
    """
    tasks = list(tasks)
    work = _WorkList(tasks)
    for _ in range(min(len(tasks), _width(pool)) - 1):
        try:
            # a helper never raises: its failure is the list's
            pool.submit(work.work)
        except RuntimeError:  # the pool was shut down: read alone
            break
    work.work()
    with external_wait("readpool.drain"):
        work._quiet.wait()
    error = work.error()
    if error is not None:
        raise error
