"""The seal-time checksum's worker: one thread beside an engine's step loop.

A block's content checksum is taken of the bytes it holds when it seals
(docs/resilience.md §Silent corruption). The engine thread reads those bytes
off the pool, by a program enqueued behind the dispatch that filled them, and
starts their copy to the host; the copy's arrival, the assembly of a sharded
set and the crc32 over each block are this thread's, so the step loop goes on
to its next dispatch. It touches neither the allocator nor the pool: what it
finishes lies here as ``(block id, seal generation, crc)`` until the engine
thread takes it (``take_done``) and registers it.

Its time lies in calls that let go of the interpreter lock (the wait for the
host copy, numpy's copy of a member, ``zlib.crc32`` over a buffer:
``kv/pages.py:checksums_at``), so it runs beside the engine thread and not
in turns with it.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, List, Optional, Tuple

from dynamo_tpu.kv import pages as kv_pages


class SealCrcWorker:
    """Jobs in, oldest first: ``(sealing, block_ids, generation, nbytes)``,
    where ``sealing.host()`` gives the host page set (waiting for its copy)
    and ``sealing.where[block_id]`` a block's position in it. The thread
    starts with the first job. ``submit``, ``take_done``, ``wait`` and
    ``close`` are the engine thread's; the counters may be read from any."""

    def __init__(self, name: str):
        self._name = name
        self._cond = threading.Condition()
        self._jobs: Deque[Tuple[Any, List[int], int, int]] = deque()
        self._done: List[Tuple[int, int, int]] = []  # (block id, generation, crc)
        self._error: Optional[Exception] = None
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        # handed over and not yet hashed, and the most blocks that ever were
        self.pending_bytes = 0
        self.pending_blocks = 0
        self.pending_peak = 0
        self.blocks_hashed = 0
        self.busy_us = 0.0  # from taking a job to publishing it, summed

    @property
    def has_done(self) -> bool:
        """Is there something for ``take_done``: values, or a failure?"""
        return bool(self._done) or self._error is not None

    def submit(self, sealing, block_ids: List[int], generation: int, nbytes: int) -> None:
        with self._cond:
            if self._closed:
                return
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True
                )
                self._thread.start()
            self._jobs.append((sealing, block_ids, generation, nbytes))
            self.pending_bytes += nbytes
            self.pending_blocks += len(block_ids)
            self.pending_peak = max(self.pending_peak, self.pending_blocks)
            self._cond.notify_all()

    def take_done(self) -> List[Tuple[int, int, int]]:
        """What has been hashed since the last call. Raises what the thread
        raised, here and in every later call: it hashes nothing after that."""
        with self._cond:
            if self._error is not None:
                raise self._error
            done, self._done = self._done, []
            return done

    def wait(self, max_bytes: int) -> None:
        """Block until at most ``max_bytes`` of pages are pending: 0 for all
        of them, a bound for the oldest. Returns at once after ``close``."""
        with self._cond:
            while (
                self.pending_bytes > max_bytes
                and self._error is None and not self._closed
            ):
                self._cond.wait()

    def close(self) -> None:
        """Stop after the job in hand and join; what is pending is dropped."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._jobs and not self._closed:
                    self._cond.wait()
                if self._closed:
                    return
                sealing, block_ids, generation, nbytes = self._jobs[0]
            t0 = time.perf_counter()
            try:
                crcs = kv_pages.checksums_at(
                    sealing.host(), [sealing.where[b] for b in block_ids]
                )
            except Exception as e:  # the engine thread's to raise: take_done
                with self._cond:
                    self._error = e
                    self._cond.notify_all()
                return
            sealing = None  # the pages go with the last job that reads them
            with self._cond:
                self._jobs.popleft()
                self._done.extend((b, generation, c) for b, c in zip(block_ids, crcs))
                self.pending_bytes -= nbytes
                self.pending_blocks -= len(block_ids)
                self.blocks_hashed += len(block_ids)
                self.busy_us += (time.perf_counter() - t0) * 1e6
                self._cond.notify_all()
