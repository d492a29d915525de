"""Outputs of large reads, in host blocks that earlier reads mapped.

glibc serves an allocation of 32 MiB or more with pages fresh from the
kernel and gives them back when it is freed, so each large read's output
would start on pages never touched.  Where transparent hugepages are
off, as on a TPU v5e host, the first write to each 4 KiB page then
faults: about 413,000 faults for a 24 h QPE window of 1.69 GB, 1.6-1.8 s
of a read whose decode takes 0.2 s on 13 threads.  More threads do not
help, since faults in one address space contend with each other.

So :func:`full` takes an output of :data:`MIN_BYTES` or more from a
process-wide :class:`OutputArena` of blocks that stay mapped between
reads, and fills it there at memory speed.  A block is handed out again
only when nothing refers to it: every array over a block (a squeezed
result, a slice a caller kept, one that JAX still copies to the device)
refers to the block's bytes as its base, so CPython's reference count of
those bytes says whether the block is free, and no caller has to give
anything back.  What the arena keeps is bounded by the most it had in
use over the last :data:`HORIZON_S` seconds.
"""

from __future__ import annotations

import math
import sys
import time
from collections import deque
from typing import Callable, List, Sequence, Tuple

import numpy as np

from repro import obs
from repro.analysis.dynamic.runtime import new_lock, note_write

# outputs of at least this many bytes come from the arena: glibc's
# largest mmap threshold on 64-bit hosts, above which every allocation
# is mapped fresh and unmapped on free
MIN_BYTES = 32 << 20
# blocks are whole 2 MiB (one huge page), so outputs of near sizes share
_UNIT = 2 << 20
# an output takes a block at most this much larger than it needs
_FIT = 1.25
# the arena keeps no more than the most bytes it had in use over the
# last minute of reads, and releases a block that served no read in it
HORIZON_S = 60.0


class _Block:
    """A kept block: its bytes, the base of every array made over it,
    and when it last served a read."""

    __slots__ = ("raw", "used_at")

    def __init__(self, nbytes: int, now: float) -> None:
        self.raw = np.empty(nbytes, dtype=np.uint8)
        self.used_at = now


def _refs(block: _Block) -> int:
    return sys.getrefcount(block.raw)


# the references an idle block's bytes have, counted as _refs counts
# them: the block's own, and none from any array
_IDLE_REFS = _refs(_Block(0, 0.0))


class OutputArena:
    """Mapped host blocks for the outputs of large reads."""

    def __init__(self, clock: Callable[[], float] = time.monotonic) -> None:
        self._clock = clock
        self._lock = new_lock("OutputArena._lock")
        self._blocks: List[_Block] = []       # idle and in use
        # (time, bytes in use) of takes within the horizon, falling in size
        self._in_use = deque()

    def full(self, shape: Sequence[int], fill_value, dtype) -> np.ndarray:
        """``np.full(shape, fill_value, dtype)``, bit for bit.

        An output of :data:`MIN_BYTES` or more is made in a kept block
        and counted as ``store.read.reused`` or ``store.read.fresh``."""
        dtype = np.dtype(dtype)
        nbytes = math.prod(shape) * dtype.itemsize
        if nbytes < MIN_BYTES:
            return np.full(shape, fill_value, dtype=dtype)
        t0 = time.perf_counter()
        with self._lock:
            raw, reused, released = self._take(nbytes)
        # the released blocks are unmapped here, outside the lock
        del released
        obs.record("store.read.reused" if reused else "store.read.fresh",
                   time.perf_counter() - t0, nbytes)
        out = raw[:nbytes].view(dtype).reshape(shape)
        # a reused block holds an earlier read's data: fill it all
        np.copyto(out, fill_value, casting="unsafe")
        return out

    def _take(self, nbytes: int) -> Tuple[np.ndarray, bool, List[_Block]]:
        """The bytes of a block for ``nbytes``, whether it was idle, and
        the idle blocks let go to keep within the bound.  Holds the
        lock."""
        size = -(-nbytes // _UNIT) * _UNIT
        now = self._clock()
        in_use, idle = 0, []
        for block in self._blocks:
            if _refs(block) > _IDLE_REFS:
                in_use += block.raw.nbytes
            else:
                idle.append(block)
        fits = [b for b in idle if size <= b.raw.nbytes <= _FIT * size]
        taken = min(fits, key=lambda b: b.raw.nbytes) if fits else None
        reused = taken is not None
        if reused:
            idle.remove(taken)
        in_use += taken.raw.nbytes if reused else size
        note_write(self, "_in_use", owner="OutputArena")
        # an entry no larger than this one can no longer be the most in
        # the horizon, so the entries fall in size and the first is it
        while self._in_use and self._in_use[-1][1] <= in_use:
            self._in_use.pop()
        self._in_use.append((now, in_use))
        while self._in_use[0][0] < now - HORIZON_S:
            self._in_use.popleft()
        room = self._in_use[0][1] - in_use
        # keep the idle blocks that served a read most lately while they
        # fit in the room; release the rest
        released = []
        for b in sorted(idle, key=lambda b: b.used_at, reverse=True):
            if b.used_at >= now - HORIZON_S and b.raw.nbytes <= room:
                room -= b.raw.nbytes
            else:
                released.append(b)
        note_write(self, "_blocks", owner="OutputArena")
        if released:
            gone = {id(b) for b in released}
            self._blocks = [b for b in self._blocks if id(b) not in gone]
        if reused:
            taken.used_at = now
        else:
            taken = _Block(size, now)
            self._blocks.append(taken)
        return taken.raw, reused, released


_ARENA = OutputArena()


def full(shape: Sequence[int], fill_value, dtype) -> np.ndarray:
    """:meth:`OutputArena.full` on the process's arena."""
    return _ARENA.full(shape, fill_value, dtype)
