"""A set of KV pages: some blocks of the pool, taken off it.

The pool is one mapping ``{member: array[L, N, bs, ...]}`` that the model
defines (``models/llama.py:make_kv_cache``: ``k`` and ``v``, and for an int8
pool their scale tables). A page set is the same mapping over ``n`` blocks,
``{member: array[L, n, bs, ...]}``, on the device or on the host. This module
is the only one that knows more about it than that: the engine, the host
tier, the integrity plane and the transfer plane hand the value on whole, so
a configuration that adds or changes a member of the pool changes
:func:`pack` / :func:`unpack` (its wire form) and nothing else above the
model.

jax is imported inside the device functions only: the transfer plane of a
process that never touches a device imports this module too.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from dynamo_tpu.runtime import integrity

Pages = Dict[str, Any]


class KvDtypeMismatch(TypeError):
    """KV pages and the target pool disagree on the storage layout (int8
    pages+scales vs native dtype). Raised instead of writing mismatched
    bytes into the pool — a dtype skew must surface as a clean typed error,
    never as silently corrupt pages. The disagg transfer plane maps it to a
    prefill-failure reply so the decode side falls back to local prefill."""


class MigrationRejected(RuntimeError):
    """A target engine refused to stage a live-migrated stream (out of KV
    blocks, block-size/page-count mismatch, history longer than its
    max_model_len). Typed so the transfer plane's ``migrate`` op nacks
    cleanly and the source degrades that stream to the ordinary resume
    path — never a torn page set (docs/resilience.md §Live migration)."""


class StateNotPortable(MigrationRejected):
    """The model keeps state per slot beside its pages (a recurrent layer's:
    ``models.module_for``), and the pages would travel without it: a prefix
    hit, a host-tier resume, a disaggregated transfer, a migration. Refused
    by name until the state is snapshotted at block boundaries (ROADMAP M5);
    a ``MigrationRejected``, so the planes that nack that cleanly nack this."""


# The members today's peers and sealed checksums know, in the order they are
# chained into a block's crc and laid into a frame.
_NATIVE = ("k", "v")
_INT8 = ("k", "v", "k_scale", "v_scale")
# a latent-attention pool (ops/latent.py): ONE member, a token's normed latent
# and shared key part in a row
_LATENT = ("latent",)


def members(pages: Mapping[str, Any]) -> List[str]:
    """The members in the one order checksums and frames use. Not the
    mapping's own: a jitted program hands a dict back with its keys sorted,
    so the pool's order does not survive its first dispatch."""
    return sorted(
        pages, key=lambda m: (_INT8.index(m) if m in _INT8 else len(_INT8), m)
    )


def count(pages: Mapping[str, Any]) -> int:
    """How many blocks the set holds."""
    return next(iter(pages.values())).shape[1]


def is_native(pages: Mapping[str, Any]) -> bool:
    """Pages of a native-dtype pool: the one form every peer, however old,
    reads. Anything else goes only to a peer that has said it knows more."""
    return tuple(members(pages)) == _NATIVE


# -- the device side -----------------------------------------------------------


@functools.cache
def programs():
    """The two jitted programs, ``(take_blocks, put_blocks)`` (the names the
    device trace shows them under), built on first use. Both go
    through the pool's arrays flattened to rows under ONE index, as the step
    programs do: indexed on the block axis alone (``pool[:, ids]``,
    ``pool.at[:, ids].set``) the TPU compiler copies the whole pool into
    another layout first (tests/test_aot_compile_tpu.py holds both to this)."""
    import jax
    import jax.numpy as jnp

    def page_rows(pool, block_ids):
        """Page p of layer l is row ``l * N + p`` of ``[L * N, bs, ...]``."""
        l, n = next(iter(pool.values())).shape[:2]
        return jnp.arange(l)[:, None] * n + block_ids

    @jax.jit
    def take_blocks(pool, block_ids):
        rows = page_rows(pool, block_ids)
        # every view, then every gather: the text this program had in
        # models/llama.py, so its compile-cache entries are found again
        views = {m: a.reshape(-1, *a.shape[2:]) for m, a in pool.items()}
        return {m: view[rows] for m, view in views.items()}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def put_blocks(pool, block_ids, pages):
        l, n, bs = next(iter(pool.values())).shape[:3]
        # an id past the pool (put's padding) stays past it in every layer
        rows = jnp.where(block_ids < n, page_rows(pool, block_ids), l * n)
        # by token row, as ops/attention.py:write_kv_to_pool writes: on one
        # chip a scatter of whole pages still has the pool copied around it
        rows = (rows[..., None] * bs + jnp.arange(bs)).reshape(-1)
        return {
            m: a.reshape(-1, *a.shape[3:]).at[rows].set(
                pages[m].astype(a.dtype).reshape(-1, *a.shape[3:]), mode="drop"
            ).reshape(a.shape)
            for m, a in pool.items()
        }

    return take_blocks, put_blocks


def take(pool: Pages, block_ids: Sequence[int]) -> Pages:
    """Copy blocks ``block_ids`` of every layer out of the pool, on the
    device: ``pool[:, block_ids]`` of each member."""
    import jax.numpy as jnp

    return programs()[0](pool, jnp.asarray(block_ids, jnp.int32))


def put(pool: Pages, block_ids: Sequence[int], pages: Mapping[str, Any]) -> Pages:
    """Write ``pages`` (host numpy or device arrays) into blocks ``block_ids``
    of the pool and return the pool; the one passed in is donated.

    The page count is padded to a power of two, so at most log2(blocks)
    shapes ever compile (an unpadded count would recompile the scatter, and
    stall decode, for every distinct transfer size); the padding's index is
    past the pool and the scatter drops it. Pages are committed to the
    pool's own devices or mesh first: pages that come from another mesh
    (split-chip prefill and decode, another tp) are resharded there, where
    jit would refuse an input committed elsewhere."""
    import jax
    import jax.numpy as jnp

    check(pool, pages)
    n = len(block_ids)
    bucket = 1 << max(n - 1, 0).bit_length()
    idx = np.full((bucket,), count(pool), np.int32)
    idx[:n] = block_ids
    padded = {}
    for m, a in pages.items():
        if isinstance(a, jax.Array):
            a = jnp.pad(a, [(0, 0), (0, bucket - n)] + [(0, 0)] * (a.ndim - 2))
        else:
            a = np.asarray(a)
            wide = np.zeros((a.shape[0], bucket) + a.shape[2:], a.dtype)
            wide[:, :n] = a
            a = wide
        padded[m] = jax.device_put(a, pool[m].sharding)
    return programs()[1](pool, jnp.asarray(idx), padded)


def check(pool: Mapping[str, Any], pages: Mapping[str, Any]) -> None:
    """Do ``pages`` fit ``pool``: the same members, the same block size.
    Raises before a byte lands or the allocator hears of the pages — corrupt
    pages are strictly worse than a failed transfer."""
    if set(pool) != set(pages):
        raise KvDtypeMismatch(
            "kv_dtype skew: pool holds %s but pages carry %s" % (
                "/".join(members(pool)), "/".join(members(pages)),
            )
        )
    for m in pool:
        if pages[m].shape[2] != pool[m].shape[2]:
            raise MigrationRejected(
                f"pages have block_size {pages[m].shape[2]}, pool uses "
                f"{pool[m].shape[2]}"
            )


# -- the host side -------------------------------------------------------------


def to_host(pages: Mapping[str, Any]) -> Pages:
    """The same set as host numpy; every member's copy starts before the
    first is waited for. Host pages pass through."""
    for a in pages.values():
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()
    return {m: np.asarray(a) for m, a in pages.items()}


def block(pages: Mapping[str, Any], i: int) -> Pages:
    """Block ``i`` of a host set alone, ``{member: [L, bs, ...]}``. Copies,
    not views: a view would pin the whole set's arrays in host RAM for as
    long as any one block of it stays in the host pool."""
    return {m: np.ascontiguousarray(a[:, i]) for m, a in pages.items()}


def stack(blocks: Sequence[Mapping[str, Any]]) -> Pages:
    """Inverse of :func:`block`: single blocks back into one set."""
    return {m: np.stack([b[m] for b in blocks], axis=1) for m in blocks[0]}


def select(pages: Mapping[str, Any], idx) -> Pages:
    """The blocks at ``idx`` (a slice or a list of positions in the set)."""
    if not isinstance(idx, slice):
        idx = np.asarray(idx, np.int32)
    return {m: a[:, idx] for m, a in pages.items()}


# -- content checksums ---------------------------------------------------------


def block_checksum(one: Mapping[str, Any]) -> int:
    """The content checksum of ONE block (:func:`block`): its members'
    bytes chained in :func:`members` order."""
    return integrity.checksum(one[m] for m in members(one))


def checksums(pages: Mapping[str, Any],
              sealed: Optional[Sequence[Optional[int]]] = None) -> List[int]:
    """Per-block checksums of a host set: what every transfer tier ships
    next to the pages. ``sealed`` holds the seal-time value of each block
    whose owner has one (those catch storage rot between seal and send) and
    ``None`` or -1 elsewhere: such a block is hashed as it stands now, which
    protects the wire only."""
    return [
        int(c) if c is not None and c >= 0 else block_checksum(block(pages, i))
        for i, c in enumerate(sealed or [None] * count(pages))
    ]


def checksums_at(pages: Mapping[str, Any], cols: Sequence[int]) -> List[int]:
    """The checksums of the blocks at positions ``cols`` of a host set, the
    values :func:`checksums` gives them, computed the way a thread beside
    the engine's has to: each member of a block is copied once into one
    contiguous buffer and hashed from there as bytes. numpy's copy and
    ``zlib.crc32`` over a buffer both let go of the interpreter lock, which a
    ``tobytes()`` does not, so between two blocks the thread holds it for a
    few calls and no longer."""
    arrays = [pages[m] for m in members(pages)]
    crcs = []
    for c in cols:
        crc = 0
        for a in arrays:
            # bytes, because bf16 (ml_dtypes) has no buffer form of its own
            crc = zlib.crc32(np.ascontiguousarray(a[:, c]).view(np.uint8), crc)
        crcs.append(crc)
    return crcs


def verify(pages: Mapping[str, Any], crcs: Optional[Sequence[Optional[int]]],
           where: str = "") -> None:
    """Hold a received host set to its travelling checksums
    (:func:`integrity.verify`, which says what is skipped)."""
    integrity.verify(
        lambda i: block_checksum(block(pages, i)), count(pages), crcs, where
    )


# -- the wire ------------------------------------------------------------------


def _wire_members(pages: Mapping[str, Any]) -> Tuple[str, ...]:
    names = tuple(members(pages))
    if names not in (_NATIVE, _INT8, _LATENT):
        raise KvDtypeMismatch("no wire form for pages of " + "/".join(names))
    return names


def arrays(pages: Mapping[str, Any]) -> List[Any]:
    """The members as the list the device plane stages."""
    return [pages[m] for m in _wire_members(pages)]


def from_arrays(pulled: Sequence[Any]) -> Pages:
    """Inverse of :func:`arrays`, for a list the device plane pulled: two
    arrays are a native set, four an int8 one, one a latent one."""
    names = {len(_NATIVE): _NATIVE, len(_INT8): _INT8, len(_LATENT): _LATENT}.get(len(pulled))
    if names is None:
        raise KvDtypeMismatch(f"no page set is {len(pulled)} arrays")
    return dict(zip(names, pulled))


def pack(pages: Mapping[str, Any], crcs: Optional[Sequence[int]] = None
         ) -> Tuple[dict, bytes]:
    """Frame header fields and body of a host set. Body layout: k | v |
    k_scale | v_scale (k and v are always the same dtype and shape, as are
    the two scale tables, so two byte lengths describe all four segments).
    A header WITHOUT ``kv_dtype`` is exactly the pre-int8 wire form: old
    peers reading a native-pool frame see no difference, and a new reader
    treats their frames as scale-less. ``crcs`` (per-block content
    checksums, docs/resilience.md §Silent corruption) is the same kind of
    optional extension: frames without it — pre-integrity peers,
    DYN_TPU_KV_INTEGRITY=0 senders — still parse everywhere; receivers
    simply cannot verify them. A latent set (one member) is its one segment
    under ``members: ["latent"]`` and WITHOUT ``k_bytes``: a peer that knows
    only k and v fails on the missing key and never injects."""
    names = _wire_members(pages)
    # bfloat16 is no standard numpy dtype everywhere: raw bytes and a dtype
    # string (ml_dtypes gives numpy bfloat16 in this stack)
    raw = [np.asarray(pages[m]).tobytes() for m in names]
    if names == _LATENT:
        latent = pages["latent"]
        header = {"members": list(names), "dtype": latent.dtype.name,
                  "shape": list(latent.shape)}
        if crcs is not None:
            header["crcs"] = [int(c) for c in crcs]
        return header, raw[0]
    k = pages["k"]
    header = {
        "dtype": k.dtype.name, "shape": list(k.shape), "k_bytes": len(raw[0]),
    }
    if names == _INT8:
        ks = pages["k_scale"]
        header["kv_dtype"] = "int8"
        header["scale_dtype"] = ks.dtype.name
        header["scale_shape"] = list(ks.shape)
        header["ks_bytes"] = len(raw[2])
    if crcs is not None:
        header["crcs"] = [int(c) for c in crcs]
    return header, b"".join(raw)


def unpack(header: Mapping[str, Any], body: bytes) -> Pages:
    """Inverse of :func:`pack`: a native set for a frame without
    ``kv_dtype`` (a pre-int8 peer's included), an int8 set otherwise, a
    latent set for a frame that names its one member."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    def segment(at, size, dtype, shape):
        return np.frombuffer(
            body[at : at + size], dtype=np.dtype(dtype)
        ).reshape(shape)

    if tuple(header.get("members") or ()) == _LATENT:
        return {"latent": segment(0, len(body), header["dtype"], header["shape"])}
    n, dt, shape = header["k_bytes"], header["dtype"], header["shape"]
    pages = {"k": segment(0, n, dt, shape), "v": segment(n, n, dt, shape)}
    if header.get("kv_dtype") == "int8":
        s = header["ks_bytes"]
        sdt, sshape = header["scale_dtype"], header["scale_shape"]
        pages["k_scale"] = segment(2 * n, s, sdt, sshape)
        pages["v_scale"] = segment(2 * n + s, s, sdt, sshape)
    return pages
