"""The seal-time checksum is computed beside the engine thread
(engine_jax/seal_crc.py): the engine thread reads a sealed block's bytes off
the pool and hands them over, a worker hashes them, and whoever reads a
block's crc waits for it.

The engine is driven one host step at a time on the test's own thread, which
so stands for the engine thread (as tests/test_chunk_rows.py does); the worker
is the real thread. Where the order matters the worker is held at a gate: its
hash (``kv_pages.checksums_at``) waits for an event the test sets, at once or
from a timer. Tiny model, float32, CPU."""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine_jax import engine as engine_mod
from dynamo_tpu.engine_jax.engine import JaxServingEngine
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.models.llama import init_params
from dynamo_tpu.runtime import integrity
from dynamo_tpu.runtime.profiling import P_SEAL_CRC

from .dense_harness import CFG, MIXED, POOLS, mesh_engine, prompt_of, serve_schedule
from .dense_harness import CHUNK_ROWS_CFG as ENGINE_CFG
from .dense_harness import pool as _pool
from .step_programs import busy, run_out, step, submit

BLOCK = ENGINE_CFG.kv_block_size
WORKER = "jax-engine-seal-crc"


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def shared(params):
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.fixture()
def eng(shared):
    assert not busy(shared)
    backlog = shared._seal_backlog_bytes
    yield shared
    shared._seal_backlog_bytes = backlog
    run_out(shared)
    step(shared)  # a host step with no slot live settles what is pending
    assert shared.allocator.active_blocks == 0 and not shared._zombie_allocs
    assert not shared.allocator._crc_pending


class Gate:
    """Holds the worker before each job's hash until it is let through."""

    def __init__(self, monkeypatch):
        self._open = threading.Event()
        self._passes = threading.Semaphore(0)
        self.jobs = 0
        hashed = kv_pages.checksums_at

        def gated(pages, cols):
            if not self._open.is_set():
                assert self._passes.acquire(timeout=60), "the gate never opened"
            self.jobs += 1
            return hashed(pages, cols)

        monkeypatch.setattr(kv_pages, "checksums_at", gated)

    def open(self):
        self._open.set()
        self._passes.release()

    def open_in(self, seconds):
        timer = threading.Timer(seconds, self.open)
        timer.daemon = True
        timer.start()

    def pass_one(self):
        self._passes.release()


@pytest.fixture()
def gate(monkeypatch):
    g = Gate(monkeypatch)
    yield g
    g.open()


def plain_crcs(eng, block_ids):
    """What the synchronous path gave: the plain read, `kv_pages.checksums`."""
    return kv_pages.checksums(eng.extract_blocks(list(block_ids)))


def settled(worker, limit=60.0):
    t0 = time.monotonic()
    while worker.pending_blocks and time.monotonic() - t0 < limit:
        time.sleep(0.002)
    assert not worker.pending_blocks


def seal_through_the_allocator(eng, n_blocks, salt):
    """Seal ``n_blocks`` fresh blocks as `note_tokens_computed` does for a
    prompt that was just computed; returns (allocation, sealed block ids)."""
    toks = prompt_of(n_blocks * BLOCK + 1, salt)
    alloc = eng.allocator.allocate_sequence(toks)
    eng.allocator.note_tokens_computed(alloc, toks[: n_blocks * BLOCK])
    return alloc, alloc.block_ids[:n_blocks]


# -- the values ------------------------------------------------------------------


@pytest.mark.parametrize("kind", POOLS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_checksums_at_gives_the_values_checksums_gives(kind, dtype):
    """One contiguous copy of a member hashed as bytes, against `block` and
    `tobytes`: the same crc for every block, whatever is asked for."""
    pool = {
        m: np.asarray(a.astype(dtype) if a.dtype == jnp.float32 else a)
        for m, a in _pool(kind).items()
    }
    want = kv_pages.checksums(pool)
    assert kv_pages.checksums_at(pool, range(kv_pages.count(pool))) == want
    assert kv_pages.checksums_at(pool, [5, 0, 5]) == [want[5], want[0], want[5]]


@pytest.mark.parametrize("kind, tp", [(k, 1) for k in POOLS] + [("native", 2)])
def test_the_workers_crcs_are_the_plain_reads(params, kind, tp):
    """Native, int8 and latent pools, on one device and sharded over a tp=2
    mesh (the worker assembles the shards): what the worker publishes for a
    seal is `kv_pages.checksums` of the same blocks read plainly."""
    eng = JaxServingEngine(CFG, params, ENGINE_CFG) if tp == 1 else mesh_engine(params, tp=tp)
    try:
        pool = _pool(kind)
        if tp > 1:
            pool = {m: jax.device_put(a, eng.cache[m].sharding) for m, a in pool.items()}
        eng.cache = pool
        ids = [7, 0, 3, 9]
        eng._seal_crcs(ids, 5)
        eng._seal_crcs([4], 6)
        eng._crc_worker.wait(0)
        got = eng._crc_worker.take_done()
        assert [(b, g) for b, g, _ in got] == [(7, 5), (0, 5), (3, 5), (9, 5), (4, 6)]
        assert [c for _, _, c in got] == plain_crcs(eng, ids + [4])
        assert eng.metrics_snapshot()["seal_crc_blocks_offthread"] == 5
    finally:
        eng.close()


def test_a_served_run_hashes_every_sealed_block_off_the_engine_thread(eng):
    before = eng.metrics_snapshot()
    serve_schedule(eng, MIXED, salt=700)
    m = eng.metrics_snapshot()
    sealed = m["seal_crc_blocks"] - before["seal_crc_blocks"]
    assert sealed >= sum(n // BLOCK for _, n, _, _ in MIXED)
    assert m["seal_crc_blocks_offthread"] - before["seal_crc_blocks_offthread"] == sealed
    assert m["seal_crc_worker_us"] > before["seal_crc_worker_us"]
    assert m["seal_crc_pending_peak"] >= 1
    for waits in ("seal_crc_reader_waits", "seal_crc_backlog_waits"):
        assert m[waits] == before[waits]
    # the last stream's end found the registry whole, and every value in it
    # is the plain read's
    registry = dict(eng.allocator._crc_of)
    assert not eng.allocator._crc_pending and len(registry) >= sealed
    assert list(registry.values()) == plain_crcs(eng, registry)


# -- what may not be registered ----------------------------------------------------


def test_a_block_resealed_while_its_first_crc_is_pending_ends_with_the_second_contents(eng, gate):
    alloc, (bid,) = seal_through_the_allocator(eng, 1, salt=710)
    first = plain_crcs(eng, [bid])[0]
    # the block's content is replaced (as a new owner's prefill would) and it
    # seals again, the first crc still with the worker
    eng.allocator.free_sequence(alloc)
    eng.allocator._unregister(bid)
    assert not eng.allocator.crc_pending(bid)
    eng.inject_blocks([bid], {m: a + 1 for m, a in eng.extract_blocks([bid]).items()})
    eng.allocator._free.append(bid)  # the next allocation takes it again
    again, (rebid,) = seal_through_the_allocator(eng, 1, salt=711)
    assert rebid == bid and eng.allocator.crc_pending(bid)
    second = plain_crcs(eng, [bid])[0]
    assert second != first

    gate.pass_one()  # the first seal's crc lands: of bytes the page no longer holds
    while gate.jobs < 1 or not eng._crc_worker.has_done:
        time.sleep(0.002)
    eng._seal_land()
    assert bid not in eng.allocator._crc_of and eng.allocator.crc_pending(bid)
    gate.open()
    assert eng.allocator.crc_of_block(bid) == second
    eng.allocator.free_sequence(again)


def test_a_block_unregistered_before_its_crc_arrives_drops_it(eng, gate):
    alloc, (bid,) = seal_through_the_allocator(eng, 1, salt=715)
    eng.allocator.free_sequence(alloc)
    eng.allocator._unregister(bid)
    eng.allocator._free.append(bid)
    gate.open()
    settled(eng._crc_worker)
    eng._seal_land()
    assert eng.allocator.crc_of_block(bid) == -1 and bid not in eng.allocator._crc_of


def test_a_watchdog_trip_still_seals_nothing(eng):
    blocks = eng.seal_crc_blocks
    seq = submit(eng, prompt_of(10 * BLOCK + 2, 718), 6)
    step(eng)
    sealed = eng.seal_crc_blocks
    assert sealed > blocks and seq.prefill_pos is not None  # the first rows' blocks
    eng._watchdog_trip(seq)
    integrity.reset_for_tests()  # the trip is this test's, not the process's
    run_out(eng)
    assert eng.seal_crc_blocks == sealed


# -- who waits ---------------------------------------------------------------------


def test_crc_of_block_waits_for_a_pending_crc_and_is_counted(eng, gate):
    alloc, bids = seal_through_the_allocator(eng, 2, salt=720)
    waits, waited, phase = (
        eng.seal_crc_reader_waits, eng.seal_crc_reader_wait_us, eng._clock.us[P_SEAL_CRC]
    )
    assert all(eng.allocator.crc_pending(b) for b in bids)
    gate.open_in(0.15)
    t0 = time.perf_counter()
    got = eng.block_crcs_of(bids)
    held = (time.perf_counter() - t0) * 1e6
    assert got == plain_crcs(eng, bids) and -1 not in got
    assert held > 100e3
    # one wait brought both; it is the engine thread's time on the checksum
    assert eng.seal_crc_reader_waits == waits + 1
    assert eng.seal_crc_reader_wait_us - waited > 100e3
    assert eng._clock.us[P_SEAL_CRC] - phase > 100e3
    eng.allocator.free_sequence(alloc)


def test_a_crc_finished_and_not_yet_registered_is_no_wait(eng):
    alloc, (bid,) = seal_through_the_allocator(eng, 1, salt=725)
    settled(eng._crc_worker)
    waits = eng.seal_crc_reader_waits
    assert eng.allocator.crc_pending(bid)  # hashed, not yet landed
    assert eng.allocator.crc_of_block(bid) == plain_crcs(eng, [bid])[0]
    assert eng.seal_crc_reader_waits == waits
    eng.allocator.free_sequence(alloc)


def test_an_eviction_into_the_host_tier_carries_the_seal_time_crc(params, gate):
    eng = JaxServingEngine(CFG, params, dataclasses.replace(
        ENGINE_CFG, num_kv_blocks=6, host_cache_blocks=16, max_model_len=40))
    try:
        alloc, bids = seal_through_the_allocator(eng, 2, salt=730)
        want = plain_crcs(eng, bids)
        hashes = [eng.allocator.hash_of_block(b) for b in bids]
        eng.allocator.free_sequence(alloc)  # cached, their crcs still pending
        gate.open_in(0.1)
        # six blocks, two cached: five fresh ones evict the older of them
        other = eng.allocator.allocate_sequence(prompt_of(5 * BLOCK, 731))
        assert other is not None and eng.seal_crc_reader_waits == 1
        eng._harvest_spills(force=True)
        block, crc = eng.host_pool.get(hashes[0])
        assert crc == want[0] == kv_pages.block_checksum(block)
        eng.allocator.free_sequence(other)
    finally:
        eng.close()


def test_extract_for_migration_waits_and_ships_the_seal_time_values(eng, gate):
    seq = submit(eng, prompt_of(2 * BLOCK + 3, 740), 24)
    while len(seq.generated) < 2:
        step(eng)
    assert eng.allocator._crc_pending
    waits = eng.seal_crc_reader_waits
    (ckpt,) = eng.export_migratable()
    gate.open_in(0.1)
    pages, crcs = eng.extract_for_migration(ckpt["request_id"])
    assert eng.seal_crc_reader_waits == waits + 1
    assert crcs == kv_pages.checksums(pages) and len(crcs) == ckpt["n_blocks"]
    sealed = seq.alloc.block_ids[: seq.alloc.sealed_blocks]
    assert sealed and crcs[: len(sealed)] == [eng.allocator._crc_of[b] for b in sealed]
    eng.abort_migration(ckpt["request_id"], "the test's")


def test_the_bound_on_pending_pages_holds_the_engine_thread(eng, gate):
    eng._seal_backlog_bytes = 2 * eng._block_bytes
    waits = eng.seal_crc_backlog_waits
    one, _ = seal_through_the_allocator(eng, 2, salt=750)  # at the bound: goes on
    assert eng.seal_crc_backlog_waits == waits
    assert eng._crc_worker.pending_bytes == eng._seal_backlog_bytes
    gate.open_in(0.15)
    t0 = time.perf_counter()
    two, _ = seal_through_the_allocator(eng, 1, salt=751)  # past it: the oldest first
    assert eng.seal_crc_backlog_waits == waits + 1 and time.perf_counter() - t0 > 0.1
    assert eng._crc_worker.pending_bytes <= eng._seal_backlog_bytes
    for alloc in (one, two):
        eng.allocator.free_sequence(alloc)


# -- the phase clock ---------------------------------------------------------------


def test_the_seal_crc_phase_is_the_hand_over_and_the_waits_alone(eng, monkeypatch):
    """`host_phase_us.seal_crc` (what the benchmark's `seal_crc_host_share`
    reads) is the engine thread's time: with a worker that takes 50 ms a job,
    a served run charges it the hand-overs while streams are live, and the
    wait where the last stream's end settles what is pending."""
    serve_schedule(eng, MIXED, salt=760)  # every program and take shape compiled
    hashed, jobs = kv_pages.checksums_at, []

    def slow(pages, cols):
        time.sleep(0.05)
        jobs.append(len(cols))
        return hashed(pages, cols)

    monkeypatch.setattr(kv_pages, "checksums_at", slow)
    phase = lambda: eng.metrics_snapshot()["host_phase_us"]["seal_crc"]
    before = phase()
    live = []

    def on_step(t, seqs):
        if any(eng._slots):
            live.append(phase())

    serve_schedule(eng, MIXED, on_step=on_step, salt=770)
    assert sum(jobs) >= sum(n // BLOCK for _, n, _, _ in MIXED)
    # while a slot was live: no wait, so far under the worker's 50 ms a job
    assert live and live[-1] - before < 0.2 * 50e3 * len(jobs)
    # the end of the last stream waited for what was pending
    assert phase() - live[-1] > 50e3


def test_the_worker_loses_and_doubles_nothing_under_a_short_switch_interval():
    """The engine thread's side (submit, take_done, wait at a bound) against
    the worker's, the interpreter switching threads every few microseconds:
    every block of every job comes back once, under its generation, with the
    crc of its bytes, and the pending counts return to zero."""
    import sys
    from dynamo_tpu.engine_jax.seal_crc import SealCrcWorker

    class Set:
        def __init__(self, host):
            self.where, self._pages = {b: j for j, b in enumerate(host)}, {
                "k": np.stack([np.full((2, 4, 3), b, np.float32) for b in host], axis=1)
            }

        def host(self):
            return self._pages

    interval, worker, got = sys.getswitchinterval(), SealCrcWorker("stress"), []
    sys.setswitchinterval(1e-5)
    try:
        deadline = time.monotonic() + 60
        for generation in range(400):
            ids = [generation * 3 + j for j in range(3)]
            worker.submit(Set(ids), ids, generation, 96 * len(ids))
            if worker.pending_bytes > 96 * 12:
                worker.wait(96 * 12)
            got += worker.take_done()
            assert time.monotonic() < deadline
        worker.wait(0)
        got += worker.take_done()
    finally:
        sys.setswitchinterval(interval)
        worker.close()
    assert not worker._thread.is_alive()
    assert worker.pending_blocks == worker.pending_bytes == 0
    assert worker.blocks_hashed == 1200 and 0 < worker.pending_peak <= 12 + 3
    want = {
        b: kv_pages.checksums({"k": np.full((2, 1, 4, 3), b, np.float32)})[0]
        for b in range(1200)
    }
    assert [(b, g) for b, g, _ in got] == [(b, b // 3) for b in range(1200)]
    assert {b: c for b, _, c in got} == want


# -- failure, the thread's life, the gate ------------------------------------------


def test_a_worker_that_raises_fails_the_next_host_step(params, monkeypatch):
    def boom(pages, cols):
        raise RuntimeError("the hash broke")

    monkeypatch.setattr(kv_pages, "checksums_at", boom)
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        submit(eng, prompt_of(3 * BLOCK + 2, 780), 16)
        step(eng)  # the first chunk's blocks seal: the worker raises beside it
        t0 = time.monotonic()
        while not eng._crc_worker.has_done and time.monotonic() - t0 < 60:
            time.sleep(0.002)
        with pytest.raises(RuntimeError, match="the hash broke"):
            eng._host_step(eng._clock)
    finally:
        eng.close()
    assert not eng._crc_worker._thread.is_alive()


def test_close_joins_the_worker(params):
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    assert eng._crc_worker._thread is None  # it starts with the first seal
    alloc, _ = seal_through_the_allocator(eng, 1, salt=790)
    thread = eng._crc_worker._thread
    assert thread.is_alive() and thread.name == WORKER and thread.daemon
    eng.close()
    assert not thread.is_alive()
    # a seal after the close starts none and holds nobody
    eng._seal_crcs(alloc.block_ids[:1], 99)
    eng._seal_await()
    assert eng._crc_worker._thread is thread


def test_with_the_integrity_plane_off_no_worker_is_built(params, monkeypatch):
    """DYN_TPU_KV_INTEGRITY=0 stays THE gate: no worker, no thread, no queue,
    no counter."""
    monkeypatch.setenv("DYN_TPU_KV_INTEGRITY", "0")

    def boom(*a, **kw):
        raise AssertionError("constructed with the integrity plane off")

    monkeypatch.setattr(engine_mod, "SealCrcWorker", boom)
    monkeypatch.setattr(kv_pages, "checksums_at", boom)
    workers = sum(t.name == WORKER for t in threading.enumerate())
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        assert eng._crc_worker is None and eng.allocator._await_crc is None
        serve_schedule(eng, MIXED[:3], salt=800)
        assert not eng.allocator._crc_of and not eng.allocator._crc_pending
        assert not any(k.startswith("seal_crc") for k in eng.metrics_snapshot())
        assert eng.block_crcs_of([0, 1]) == [-1, -1]
        assert sum(t.name == WORKER for t in threading.enumerate()) == workers
    finally:
        eng.close()


def test_a_process_spanning_mesh_seals_unchecked_and_has_no_worker(params, monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(engine_mod, "SealCrcWorker", lambda *a: pytest.fail("built"))
    eng = mesh_engine(params, tp=2)
    try:
        assert eng._multihost and not eng._seal_checksums and eng._crc_worker is None
        assert eng.metrics_snapshot()["kv_seal_checksums"] == 0
    finally:
        eng.close()
