"""Global radix (prefix) tree over chained KV block hashes.

Each node is one block in a hash chain; `workers` records which workers hold
that block. `find_matches` walks a request's hash chain from the root and
scores each worker by the length of its *contiguous* cached prefix.

Capability parity with the reference's RadixTree/KvIndexer
(kv_router/indexer.rs:239-677). Two implementations with one interface:
the C++ tree (native/radix_tree.cc, ctypes, the perf path — mirroring the
reference's native/Python split) selected by ``make_indexer()`` when the
toolchain is available, and this portable lock-guarded Python tree.
Differential-tested against each other in tests/test_native.py.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Dict, Iterable, List, Optional, Sequence

from dynamo_tpu.kv.tokens import compute_block_hashes_for_seq
from dynamo_tpu.kv_router.protocols import (
    KvCacheEvent,
    RemovedBlocks,
    RouterEvent,
    StoredBlocks,
)

OverlapScores = Dict[str, int]  # worker_id → contiguous matched blocks


class _Node:
    __slots__ = ("block_hash", "parent", "children", "workers")

    def __init__(self, block_hash: Optional[int], parent: Optional["_Node"]):
        self.block_hash = block_hash
        self.parent = parent
        self.children: Dict[int, _Node] = {}
        self.workers: set = set()


class RadixTree:
    """Single-threaded prefix tree; see KvIndexer for the locked wrapper."""

    def __init__(self):
        self.root = _Node(None, None)
        self._by_hash: Dict[int, _Node] = {}
        self.event_count = 0

    # -- queries -------------------------------------------------------------

    def find_matches(self, sequence_hashes: Sequence[int]) -> OverlapScores:
        scores: OverlapScores = {}
        node = self.root
        current: Optional[set] = None  # workers contiguous so far
        for h in sequence_hashes:
            child = node.children.get(h)
            if child is None:
                break
            current = set(child.workers) if current is None else current & child.workers
            if not current:
                break
            for w in current:
                scores[w] = scores.get(w, 0) + 1
            node = child
        return scores

    def workers(self) -> set:
        out = set()
        stack = [self.root]
        while stack:
            n = stack.pop()
            out |= n.workers
            stack.extend(n.children.values())
        return out

    # -- mutation ------------------------------------------------------------

    def apply_event(self, event: RouterEvent) -> None:
        self.event_count += 1
        data = event.event.data
        if isinstance(data, StoredBlocks):
            self._apply_stored(event.worker_id, data)
        elif isinstance(data, RemovedBlocks):
            self._apply_removed(event.worker_id, data)

    def _apply_stored(self, worker: str, data: StoredBlocks) -> None:
        if data.parent_hash is None:
            node = self.root
        else:
            node = self._by_hash.get(data.parent_hash)
            if node is None:
                # parent chain unknown (e.g. events arrived out of order or
                # after a restart): root the fragment so its hashes still match
                node = self.root
        for blk in data.blocks:
            child = node.children.get(blk.block_hash)
            if child is None:
                child = _Node(blk.block_hash, node)
                node.children[blk.block_hash] = child
                self._by_hash[blk.block_hash] = child
            child.workers.add(worker)
            node = child

    def _apply_removed(self, worker: str, data: RemovedBlocks) -> None:
        for h in data.block_hashes:
            node = self._by_hash.get(h)
            if node is None:
                continue
            node.workers.discard(worker)
            self._maybe_prune(node)

    def remove_worker(self, worker: str) -> None:
        """Purge a dead worker everywhere (lease-expiry path, indexer.rs:380)."""
        stack = list(self.root.children.values())
        doomed: List[_Node] = []
        while stack:
            n = stack.pop()
            n.workers.discard(worker)
            stack.extend(n.children.values())
            if not n.workers and not n.children:
                doomed.append(n)
        for n in doomed:
            self._maybe_prune(n)

    def _maybe_prune(self, node: _Node) -> None:
        # remove worker-less leaf chains bottom-up
        while (
            node is not self.root
            and not node.workers
            and not node.children
            and node.parent is not None
        ):
            parent = node.parent
            parent.children.pop(node.block_hash, None)
            self._by_hash.pop(node.block_hash, None)
            node = parent


class KvIndexer:
    """Thread-safe indexer over a RadixTree, keyed by token ids.

    `find_matches_for_request(token_ids)` hashes the prompt with the shared
    scheme and probes the tree (reference KvIndexer, indexer.rs:499).
    """

    def __init__(self, block_size: int, salt: Optional[bytes] = None):
        self.block_size = block_size
        self.salt = salt
        self._tree = RadixTree()
        self._lock = threading.Lock()

    def apply_event(self, event: RouterEvent) -> None:
        with self._lock:
            self._tree.apply_event(event)

    def apply_events(self, events: Iterable[RouterEvent]) -> None:
        with self._lock:
            for e in events:
                self._tree.apply_event(e)

    def remove_worker(self, worker: str) -> None:
        with self._lock:
            self._tree.remove_worker(worker)

    def find_matches(self, sequence_hashes: Sequence[int]) -> OverlapScores:
        with self._lock:
            return self._tree.find_matches(sequence_hashes)

    def find_matches_for_request(self, token_ids: Sequence[int]) -> OverlapScores:
        hashes = compute_block_hashes_for_seq(token_ids, self.block_size, self.salt)
        return self.find_matches(hashes)

    @property
    def event_count(self) -> int:
        return self._tree.event_count


class NativeKvIndexer:
    """KvIndexer backed by the C++ radix tree (native/radix_tree.cc).

    Same interface and semantics as :class:`KvIndexer`; worker-id strings
    are interned to uint64 handles for the C ABI.
    """

    MAX_WORKERS_OUT = 4096

    def __init__(self, lib, block_size: int, salt: Optional[bytes] = None):
        self.block_size = block_size
        self.salt = salt
        self._lib = lib
        self._configure(lib)
        self._tree = lib.dyn_radix_create()
        self._lock = threading.Lock()
        self._worker_to_id: Dict[str, int] = {}
        self._id_to_worker: Dict[int, str] = {}
        self._out_workers = (ctypes.c_uint64 * self.MAX_WORKERS_OUT)()
        self._out_scores = (ctypes.c_uint32 * self.MAX_WORKERS_OUT)()

    @staticmethod
    def _configure(lib) -> None:
        if getattr(lib, "_dyn_radix_configured", False):
            return
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.dyn_radix_create.restype = ctypes.c_void_p
        lib.dyn_radix_destroy.argtypes = [ctypes.c_void_p]
        lib.dyn_radix_event_count.argtypes = [ctypes.c_void_p]
        lib.dyn_radix_event_count.restype = ctypes.c_uint64
        lib.dyn_radix_apply_stored.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64, u64p,
            ctypes.c_size_t, ctypes.c_uint64,
        ]
        lib.dyn_radix_apply_removed.argtypes = [
            ctypes.c_void_p, u64p, ctypes.c_size_t, ctypes.c_uint64,
        ]
        lib.dyn_radix_remove_worker.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.dyn_radix_find_matches.argtypes = [
            ctypes.c_void_p, u64p, ctypes.c_size_t, u64p,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
        ]
        lib.dyn_radix_find_matches.restype = ctypes.c_size_t
        lib._dyn_radix_configured = True

    def __del__(self):
        tree = getattr(self, "_tree", None)
        if tree:
            self._lib.dyn_radix_destroy(tree)
            self._tree = None

    def _intern(self, worker: str) -> int:
        wid = self._worker_to_id.get(worker)
        if wid is None:
            wid = len(self._worker_to_id) + 1
            self._worker_to_id[worker] = wid
            self._id_to_worker[wid] = worker
        return wid

    @staticmethod
    def _hash_array(hashes: Sequence[int]):
        n = len(hashes)
        arr = (ctypes.c_uint64 * n)()
        for i, h in enumerate(hashes):
            arr[i] = h & 0xFFFFFFFFFFFFFFFF
        return arr, n

    def apply_event(self, event: RouterEvent) -> None:
        with self._lock:
            self._apply_locked(event)

    def apply_events(self, events: Iterable[RouterEvent]) -> None:
        with self._lock:
            for e in events:
                self._apply_locked(e)

    def _apply_locked(self, event: RouterEvent) -> None:
        data = event.event.data
        wid = self._intern(event.worker_id)
        if isinstance(data, StoredBlocks):
            arr, n = self._hash_array([b.block_hash for b in data.blocks])
            parent = data.parent_hash
            self._lib.dyn_radix_apply_stored(
                self._tree, int(parent is not None),
                (parent or 0) & 0xFFFFFFFFFFFFFFFF, arr, n, wid,
            )
        elif isinstance(data, RemovedBlocks):
            arr, n = self._hash_array(data.block_hashes)
            self._lib.dyn_radix_apply_removed(self._tree, arr, n, wid)

    def remove_worker(self, worker: str) -> None:
        with self._lock:
            wid = self._worker_to_id.get(worker)
            if wid is not None:
                self._lib.dyn_radix_remove_worker(self._tree, wid)

    def find_matches(self, sequence_hashes: Sequence[int]) -> OverlapScores:
        with self._lock:
            arr, n = self._hash_array(sequence_hashes)
            while True:
                cap = len(self._out_workers)
                k = self._lib.dyn_radix_find_matches(
                    self._tree, arr, n, self._out_workers, self._out_scores, cap
                )
                if k < cap:
                    break
                # possibly truncated (>= cap workers share the prefix): grow
                # the output buffers and re-probe so no worker is dropped
                self._out_workers = (ctypes.c_uint64 * (cap * 2))()
                self._out_scores = (ctypes.c_uint32 * (cap * 2))()
            return {
                self._id_to_worker[self._out_workers[i]]: int(self._out_scores[i])
                for i in range(k)
            }

    def find_matches_for_request(self, token_ids: Sequence[int]) -> OverlapScores:
        hashes = compute_block_hashes_for_seq(token_ids, self.block_size, self.salt)
        return self.find_matches(hashes)

    @property
    def event_count(self) -> int:
        return int(self._lib.dyn_radix_event_count(self._tree))


def make_indexer(block_size: int, salt: Optional[bytes] = None):
    """The framework's indexer factory: C++ tree when buildable, else the
    portable Python tree (interfaces are identical)."""
    from dynamo_tpu import native

    lib = native.load("radix_tree")
    if lib is not None:
        return NativeKvIndexer(lib, block_size, salt)
    return KvIndexer(block_size, salt)

