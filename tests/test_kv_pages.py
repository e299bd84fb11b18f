"""kv/pages.py: a set of KV pages is one value.

Three pools go through every leg a page set travels — off the pool, to the
host, checksummed, framed, verified, through the host tier, back into a pool
— and come back byte for byte: the native pool, the int8 pool, and a made-up
third whose dict has a member of another rank and dtype. The third is the
proof of the seam: nothing outside ``kv/pages.py`` names a member, so a
configuration that adds one (ROADMAP M2-M6) edits that module's wire form
and nothing above the model. The golden cases hold today's two layouts to
the bytes and checksums the parent commit produced.
"""

import concurrent.futures
import hashlib
import json
import zlib

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from dynamo_tpu.engine_jax.allocator import HostKvPool
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.kv.pages import KvDtypeMismatch, MigrationRejected
from dynamo_tpu.models.llama import init_params
from dynamo_tpu.runtime.integrity import KvIntegrityError

from .dense_harness import BLOCK, CFG, N_BLOCKS, POOLS
from .dense_harness import filled as _filled
from .dense_harness import pool as _pool

def _bytes(pages):
    return {m: np.asarray(a).tobytes() for m, a in pages.items()}


@pytest.mark.parametrize("kind", POOLS)
def test_a_page_set_goes_round_every_leg_and_comes_back_the_same(kind):
    pool = _pool(kind)
    ids = [7, 0, 3, 9, 4]  # five blocks: put pads them to eight
    want = {m: np.asarray(a)[:, ids] for m, a in pool.items()}

    taken = kv_pages.take(pool, ids)
    assert set(taken) == set(pool)
    assert all(isinstance(a, jax.Array) for a in taken.values())
    host = kv_pages.to_host(taken)
    assert _bytes(host) == _bytes(want)
    assert kv_pages.count(host) == len(ids)

    crcs = kv_pages.checksums(host)
    assert len(set(crcs)) == len(ids)
    if kind == "latent":
        # no configuration has defined this layout's wire form yet: the
        # frame refuses it typed, the rest of the chain does without
        with pytest.raises(KvDtypeMismatch, match="latent"):
            kv_pages.pack(host, crcs)
        with pytest.raises(KvDtypeMismatch):
            kv_pages.arrays(host)
        landed = host
    else:
        header, body = kv_pages.pack(host, crcs)
        # what a peer reads: the header as JSON, the body as it came
        landed = kv_pages.unpack(json.loads(json.dumps(header)), body)
        assert _bytes(landed) == _bytes(host)
        assert header["crcs"] == crcs
        assert _bytes(kv_pages.from_arrays(kv_pages.arrays(host))) == _bytes(host)
    kv_pages.verify(landed, crcs, where="test")
    kv_pages.verify(landed, None)
    for m in landed:  # one flipped bit in ANY member fails its block alone
        flipped = np.array(landed[m])
        bad = np.ascontiguousarray(flipped[:, 2])
        bad.view(np.uint8).reshape(-1)[0] ^= 0x01
        flipped[:, 2] = bad
        with pytest.raises(KvIntegrityError, match="block 2"):
            kv_pages.verify(dict(landed, **{m: flipped}), crcs)
        kv_pages.verify(
            dict(landed, **{m: flipped}), [c if i != 2 else -1 for i, c in enumerate(crcs)]
        )

    # the host tier: block by block into the LRU, a rehit checks each
    # against its seal-time checksum, the hits go back in as one set
    tier = HostKvPool(max_blocks=16)
    for i, crc in enumerate(crcs):
        tier.put(100 + i, kv_pages.block(landed, i), crc=crc)
    hits = [tier.get(100 + i) for i in range(len(ids))]
    assert [kv_pages.block_checksum(b) for b, _ in hits] == crcs
    assert all(not np.shares_memory(b[m], landed[m]) for b, _ in hits for m in b)
    back = kv_pages.stack([b for b, _ in hits])
    assert _bytes(back) == _bytes(host)

    # into another pool's blocks, host pages and device pages alike
    dest = [1, 11, 5, 2, 8]
    for pages in (back, taken):
        empty = {m: jnp.zeros_like(a) for m, a in pool.items()}
        filled = kv_pages.put(empty, dest, pages)
        assert set(filled) == set(pool)
        assert _bytes(kv_pages.to_host(kv_pages.take(filled, dest))) == _bytes(want)
        rest = [b for b in range(N_BLOCKS) if b not in dest]
        for m, a in filled.items():
            assert not np.asarray(a)[:, rest].any(), "the padding wrote a block"
    sel = kv_pages.select(host, [4, 1])
    assert _bytes(sel) == {m: a[:, [4, 1]].tobytes() for m, a in want.items()}
    assert _bytes(kv_pages.select(taken, slice(1, 3))) == {
        m: a[:, 1:3].tobytes() for m, a in want.items()
    }


@pytest.mark.parametrize("pages_of", POOLS)
@pytest.mark.parametrize("pool_of", POOLS)
def test_check_refuses_the_pages_of_any_other_pool(pool_of, pages_of):
    pool = _pool(pool_of)
    pages = kv_pages.take(_pool(pages_of), [2, 6])
    if pool_of == pages_of:
        kv_pages.check(pool, pages)
        return
    before = _bytes(pool)
    with pytest.raises(KvDtypeMismatch):
        kv_pages.check(pool, pages)
    with pytest.raises(KvDtypeMismatch):
        kv_pages.put(pool, [0, 1], pages)
    assert _bytes(pool) == before  # refused before the pool was donated


@pytest.mark.parametrize("kind", POOLS)
def test_check_refuses_another_block_size(kind):
    pages = kv_pages.to_host(kv_pages.take(_pool(kind, block=BLOCK * 2), [1]))
    with pytest.raises(MigrationRejected, match="block_size"):
        kv_pages.check(_pool(kind), pages)


# -- what the parent commit produced --------------------------------------------
#
# Frames and checksums of a 3-block page set, [L=2, n=3, bs=4, KVH=2, D=8],
# filled by _filled(): sha256 over header JSON + body as
# disagg/transfer.py:_pack_pages wrote them at commit 3cd77bc, and the crcs of
# runtime/integrity.py:page_checksums there.
GOLDEN = {
    "native": (
        "4f518bda2252bf639064f278aea38daa09261eebf8bda8ef30dc6bbabe8158fb",
        [291707601, 2924096913, 6386791],
    ),
    "int8": (
        "b70e8b35398c87de94f942439997b73a0b672b098817ecaaf50edc72b16338c5",
        [3609501901, 3153932531, 1712735697],
    ),
}


def _golden_pages(kind):
    dt = np.int8 if kind == "int8" else ml_dtypes.bfloat16
    pages = {
        "k": _filled((2, 3, 4, 2, 8), dt, 1),
        "v": _filled((2, 3, 4, 2, 8), dt, 2),
    }
    if kind == "int8":
        pages["k_scale"] = _filled((2, 3, 4), np.float32, 3)
        pages["v_scale"] = _filled((2, 3, 4), np.float32, 4)
    return pages


def _old_pack(k, v, scales, crcs):
    """The parent's frame, from its layout's description: header fields in
    this order, body k | v | k_scale | v_scale."""
    header = {"dtype": k.dtype.name, "shape": list(k.shape), "k_bytes": k.nbytes}
    body = k.tobytes() + v.tobytes()
    if scales is not None:
        header["kv_dtype"] = "int8"
        header["scale_dtype"] = scales[0].dtype.name
        header["scale_shape"] = list(scales[0].shape)
        header["ks_bytes"] = scales[0].nbytes
        body += scales[0].tobytes() + scales[1].tobytes()
    header["crcs"] = [int(c) for c in crcs]
    return header, body


def _old_crc(arrays):
    """The parent's block checksum, member by member."""
    crc = 0
    for a in arrays:
        crc = zlib.crc32(np.ascontiguousarray(a).tobytes(), crc)
    return crc


@pytest.mark.parametrize("kind", ["native", "int8"])
def test_frames_and_checksums_are_the_parents(kind):
    pages = _golden_pages(kind)
    sha, crcs = GOLDEN[kind]
    names = ("k", "v", "k_scale", "v_scale")[: len(pages)]
    assert crcs == [
        _old_crc(pages[m][:, i] for m in names) for i in range(3)
    ]
    # whatever order the dict arrives in (a jitted program sorts its keys)
    shuffled = {m: pages[m] for m in sorted(pages, reverse=True)}
    assert kv_pages.checksums(shuffled) == crcs
    assert kv_pages.block_checksum(kv_pages.block(shuffled, 1)) == crcs[1]

    header, body = kv_pages.pack(shuffled, crcs)
    scales = (pages["k_scale"], pages["v_scale"]) if kind == "int8" else None
    old_header, old_body = _old_pack(pages["k"], pages["v"], scales, crcs)
    assert json.dumps(header) == json.dumps(old_header)  # field order too
    assert body == old_body
    assert hashlib.sha256(json.dumps(header).encode() + body).hexdigest() == sha
    assert _bytes(kv_pages.unpack(old_header, old_body)) == _bytes(pages)
    # the pre-integrity form: no crcs, the header says nothing of them
    assert "crcs" not in kv_pages.pack(pages)[0]


# -- the engine hands the value on ------------------------------------------------


def _call(engine, fn, timeout=60):
    fut = concurrent.futures.Future()

    def wrap():
        try:
            fut.set_result(fn())
        except Exception as e:  # delivered to the caller
            fut.set_exception(e)

    engine.post(wrap)
    return fut.result(timeout=timeout)


@pytest.mark.parametrize("kind", POOLS)
def test_the_engine_moves_pages_it_cannot_name(kind):
    """An engine whose pool is swapped for each of the three: extraction, the
    seal-time checksums, the spill to the host tier and its harvest, the
    rehit's stack and inject, and a foreign prefix seeded from another
    worker all pass the set through. (No step program runs: those are the
    model's, and the model is where a pool's members are defined.)"""
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = JaxServingEngine(CFG, params, EngineConfig(
        max_slots=2, kv_block_size=BLOCK, max_model_len=64,
        num_kv_blocks=N_BLOCKS, host_cache_blocks=8,
    ))
    try:
        def swap(pool):
            eng.cache = pool

        pool = _pool(kind)
        want = {m: np.asarray(a) for m, a in pool.items()}
        _call(eng, lambda: swap(pool))
        out = _call(eng, lambda: eng.extract_blocks([3, 5]))
        assert _bytes(out) == {m: a[:, [3, 5]].tobytes() for m, a in want.items()}
        dev = _call(eng, lambda: eng.extract_blocks([3, 5], as_device=True))
        assert all(isinstance(a, jax.Array) for a in dev.values())

        def seal():  # the seal-time path: a plain read, hashed by the worker
            eng._seal_crcs([3, 5], 0)
            eng._crc_worker.wait(0)
            return [crc for _, _, crc in eng._crc_worker.take_done()]

        crcs = _call(eng, seal)
        assert crcs == kv_pages.checksums(out)

        # spill blocks 3 and 5, lose them on the device, hit them again
        def spill():
            eng._offload_blocks([(1003, 3, crcs[0]), (1005, 5, crcs[1])])
            eng._harvest_spills(force=True)

        _call(eng, spill)
        assert 1003 in eng.host_pool and 1005 in eng.host_pool
        _call(eng, lambda: swap({m: jnp.zeros_like(a) for m, a in pool.items()}))

        def rehit():
            from dynamo_tpu.engine_jax.allocator import SequenceAllocation
            from dynamo_tpu.kv.tokens import TokenBlockSequence

            alloc = SequenceAllocation(
                block_ids=[8, 9], cached_tokens=0,
                token_blocks=TokenBlockSequence([], BLOCK),
                host_hits=[
                    (i, h) + eng.host_pool.get(h)
                    for i, h in enumerate((1003, 1005))
                ],
            )
            eng._inject_host_hits(alloc)
            return eng.extract_blocks([8, 9])

        assert _bytes(_call(eng, rehit)) == _bytes(out)

        # a prefix read from another worker: two full blocks of tokens
        seeded = _call(eng, lambda: eng.seed_external_prefix(
            list(range(2 * BLOCK)), out
        ))
        assert seeded == 2
        other = next(k for k in POOLS if k != kind)
        foreign = kv_pages.to_host(kv_pages.take(_pool(other), [0, 1]))
        free = eng.allocator.free_blocks
        with pytest.raises(KvDtypeMismatch):
            _call(eng, lambda: eng.seed_external_prefix(
                list(range(50, 50 + 2 * BLOCK)), foreign
            ))
        assert eng.allocator.free_blocks == free  # refused before the allocator
    finally:
        eng.close()
