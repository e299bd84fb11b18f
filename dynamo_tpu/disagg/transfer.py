"""KV page transfer plane: prefill worker → decode worker HBM.

Host-staged bulk transfer over the framed TCP codec (the TPU-native
replacement for the reference's NIXL RDMA path, SURVEY.md §2.10): the
prefill side pulls computed pages to host, ships one frame
(header JSON + raw bf16/f32 bytes), and the decode side writes them into its
page pool with a donated on-device update (engine.inject_blocks). Rendezvous
is by engine_id → address in the statestore, exactly like NixlMetadataStore
(examples/llm/utils/nixl.py:58-109).
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Dict, Optional

from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.kv.pages import KvDtypeMismatch, MigrationRejected
from dynamo_tpu.runtime import faults as _FAULTS
from dynamo_tpu.runtime import integrity, tracing
from dynamo_tpu.runtime.codec import TwoPartMessage, read_frame, write_frame
from dynamo_tpu.runtime.integrity import KvIntegrityError
from dynamo_tpu.runtime.netutil import TrackedServer

logger = logging.getLogger(__name__)


class _NoDevicePeer(Exception):
    """Peer has no device plane: fall back to the host-staged path."""


# the refusal a peer from before the int8 layout gets when the pool is int8
_OLD_PEER = "kv_dtype int8: peer lacks scale-table support"


def _sender_crcs(engine, ids, pages):
    """Per-block content checksums a sender ships next to its pages:
    seal-registry values where the block is sealed (those catch storage
    rot between seal and send), extract-time values otherwise (wire-scope
    protection only). ``None`` with the integrity plane off — the header
    then omits ``crcs`` entirely (pre-integrity wire form). MUST run on
    the engine thread when ``engine`` has a crc registry."""
    if not integrity.enabled():
        return None
    sealed = (
        engine.block_crcs_of(list(ids))
        if hasattr(engine, "block_crcs_of") else None
    )
    return kv_pages.checksums(pages, sealed)


def _engine_call(engine, fn):
    """Run ``fn`` on the engine thread, await the result from asyncio.

    The resolve callbacks tolerate a future the awaiter already abandoned
    (``wait_for`` timeout, coordinator drain cancelled): the engine thread
    can be busy for seconds (compile, a long dispatch) and its late
    completion must not raise ``InvalidStateError`` into the event loop —
    first surfaced by the chaos matrix's corrupt×drain composition."""
    loop = asyncio.get_running_loop()
    fut = loop.create_future()

    def _resolve(setter, value):
        if not fut.done():
            setter(value)

    def run():
        try:
            r = fn()
        except Exception as e:  # delivered to the awaiting caller
            loop.call_soon_threadsafe(_resolve, fut.set_exception, e)
            return
        loop.call_soon_threadsafe(_resolve, fut.set_result, r)

    engine.post(run)
    return fut


class KvTransferServer:
    """Decode-worker side: receives KV pages and completes waiting requests.

    With a :class:`~dynamo_tpu.disagg.device_transfer.DevicePlane` attached
    (platforms whose PJRT backend implements the transfer-server API), the
    BULK bytes ride the device fabric instead of this TCP channel — the
    channel then carries only control: stage/pull descriptors and hash
    validation (``read_blocks_dev`` / ``kv_blocks_dev`` ops)."""

    def __init__(self, engine, host: str = "0.0.0.0", port: int = 0,
                 device_plane=None):
        self.engine = engine
        self.host = host
        self.port = port
        self.device_plane = device_plane
        self._server: Optional[TrackedServer] = None
        # label the corrupt-fault gate matches on (a drill targets ONE
        # worker's outbound pages); attach points override it with the
        # advertised transfer address
        self.fault_addr = ""

    async def start(self) -> None:
        self._server = TrackedServer(self._handle, self.host, self.port)
        self.port = await self._server.start()
        if not self.fault_addr:
            self.fault_addr = f"{self.host}:{self.port}"
        logger.info("kv transfer server on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._server:
            await self._server.stop()

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                try:
                    frame = await read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    return
                h = json.loads(frame.header)

                async def reply(**fields):
                    await write_frame(writer, TwoPartMessage(
                        json.dumps({"id": h.get("id"), **fields}).encode(), b""
                    ))

                if h.get("op") == "kv_blocks":
                    pages = kv_pages.unpack(h, frame.body)
                    # content verification BEFORE the engine sees a byte
                    # (docs/resilience.md §Silent corruption): a frame that
                    # fails its travelling checksums nacks typed — the
                    # SENDER learns its pages are rotten and counts the
                    # trip; this side falls the request back to local
                    # prefill, corrupt pages never land in the pool
                    if h.get("crcs") is not None and integrity.enabled():
                        try:
                            kv_pages.verify(
                                pages, h["crcs"], where="kv_blocks"
                            )
                        except KvIntegrityError as e:
                            integrity.note_remote_failure("kv_blocks")
                            self.engine.fail_remote_prefill(
                                h["request_id"], f"kv integrity: {e}"
                            )
                            await reply(
                                ok=False, int8=True, code="KvIntegrityError",
                                error=str(e),
                            )
                            continue
                    # dtype skew (an int8 frame into a native pool, or a
                    # pre-int8 peer's frame into an int8 pool) surfaces as a
                    # typed fallback inside complete_remote_prefill — never
                    # as corrupt pages
                    self.engine.complete_remote_prefill(
                        h["request_id"], h["first_token"], h["block_ids"],
                        pages,
                    )
                elif h.get("op") == "read_blocks":
                    # prefill worker reading this decode worker's cached
                    # prefix pages (so it computes only the suffix). Each
                    # page's registered content hash ships along so the
                    # reader can verify the pages were not freed + reused
                    # since the request was enqueued — stale reads would
                    # otherwise poison its prefix cache with wrong KV.
                    def _extract(ids=h["block_ids"]):
                        pages = self.engine.extract_blocks(ids)
                        return (
                            pages, self.engine.block_hashes_of(ids),
                            _sender_crcs(self.engine, ids, pages),
                        )

                    pages, hashes, crcs = await _engine_call(
                        self.engine, _extract
                    )
                    if not (kv_pages.is_native(pages) or h.get("int8_ok")):
                        # pre-int8 peer reading an int8 pool: its fixed
                        # two-segment unpack would misparse the 4-segment
                        # body — refuse with a typed error instead
                        await reply(ok=False, int8=True, error=_OLD_PEER)
                        continue
                    hdr, body = kv_pages.pack(pages, crcs)
                    if _FAULTS.current() is not None:
                        # wire leg of the silent-corruption drill: a rotten
                        # worker SERVING its cached pages — the flip is
                        # post-checksum, the reader's verify must catch it
                        body = _FAULTS.corrupt_pages(
                            "transfer", self.fault_addr, body
                        )
                    # "int8" advertises THIS binary's capability (not the
                    # pool's dtype): clients cache it per address so int8
                    # sends can take the device path on later transfers
                    hdr.update({"id": h.get("id"), "ok": True, "int8": True,
                                "hashes": hashes})
                    await write_frame(
                        writer, TwoPartMessage(json.dumps(hdr).encode(), body)
                    )
                    continue
                elif h.get("op") == "read_blocks_dev":
                    # device path: stage the pages on the device plane and
                    # return a pull descriptor instead of the bytes
                    if self.device_plane is None:
                        await reply(ok=False, error="no device plane")
                        continue

                    def _extract_dev(ids=h["block_ids"]):
                        return (
                            self.engine.extract_blocks(ids, as_device=True),
                            self.engine.block_hashes_of(ids),
                        )

                    pages, hashes = await _engine_call(
                        self.engine, _extract_dev
                    )
                    if not (kv_pages.is_native(pages) or h.get("int8_ok")):
                        # pre-int8 peer: it would pull the 4-array stage,
                        # keep [k, v], and inject raw int8 values as native
                        # KV — silent corruption. Refuse instead; its TCP
                        # fallback then fails loudly.
                        await reply(ok=False, int8=True, error=_OLD_PEER)
                        continue
                    uid, specs = self.device_plane.stage(
                        kv_pages.arrays(pages)
                    )
                    await reply(
                        ok=True, int8=True, uuid=uid, specs=specs,
                        hashes=hashes, dev_addr=self.device_plane.address(),
                    )
                    continue
                elif h.get("op") == "kv_blocks_dev":
                    # prefill staged its computed pages; pull them into our
                    # device memory, then inject
                    if self.device_plane is None:
                        await reply(ok=False, error="no device plane")
                        continue
                    pulled = await asyncio.to_thread(
                        self.device_plane.pull,
                        h["dev_addr"], h["uuid"], h["specs"],
                    )
                    self.engine.complete_remote_prefill(
                        h["request_id"], h["first_token"], h["block_ids"],
                        kv_pages.from_arrays(pulled),
                    )
                elif h.get("op") == "release_dev":
                    # client pulled: free the staged device arrays now
                    # instead of pinning HBM pages until the TTL sweep
                    if self.device_plane is not None:
                        self.device_plane.release(h["uuid"])
                elif h.get("op") == "migrate":
                    # live in-flight migration (docs/resilience.md §Live
                    # migration): one atomic frame = checkpoint header +
                    # packed history pages. The engine stages it (allocate +
                    # inject + seal) or raises a typed rejection — the nack
                    # below tells the source to degrade that stream to the
                    # resume path; nothing is ever partially staged.
                    pages = kv_pages.unpack(h, frame.body)
                    meta = h.get("migrate") or {}
                    # quarantine × migration composition (docs/chaos.md): a
                    # latch landing mid-ship must abort the in-flight
                    # transfer TO this process — a quarantined worker's KV
                    # pool is suspect, so adopting a foreign stream into it
                    # would hand corrupt pages a clean lineage. Checked at
                    # the receiver because the source's routing snapshot
                    # can be a beat stale; the typed nack degrades the
                    # stream to the resume path, same as any rejection.
                    if integrity.enabled() and integrity.quarantined():
                        await reply(
                            ok=False, int8=True, code="MigrationRejected",
                            error="target quarantined: refusing to stage "
                                  "migrated KV pages",
                        )
                        continue
                    try:
                        res = await _engine_call(
                            self.engine,
                            lambda: self.engine.stage_migration(meta, pages),
                        )
                    except (MigrationRejected, KvDtypeMismatch,
                            KeyError, ValueError, TypeError) as e:
                        # KvIntegrityError rides this tuple (it IS a
                        # ValueError): the nack's code tells the SOURCE its
                        # pages failed verification — it counts the trip
                        # against itself and degrades the stream to resume
                        if isinstance(e, KvIntegrityError):
                            integrity.note_remote_failure("migrate_stage")
                        await reply(
                            ok=False, int8=True, code=type(e).__name__,
                            error=str(e),
                        )
                        continue
                    await reply(ok=True, int8=True, staged=res)
                    continue
                elif h.get("op") == "prefill_failed":
                    self.engine.fail_remote_prefill(h["request_id"], h.get("message", ""))
                await reply(ok=True, int8=True)
        finally:
            writer.close()


class LocalKvTransfer:
    """Same-host prefill→decode handoff with pages staying device-resident.

    When prefill and decode engines share a process (one host's chips split
    between a prefill mesh and a decode mesh), pages move as jax arrays:
    XLA reshards them across the two meshes at the inject jit boundary —
    including differing tensor-parallel layouts, since resharding splits or
    merges the kv-head axis as needed. No host copy, no TCP. This is the
    TPU device path standing in for the reference's same-node NIXL
    GPU-to-GPU transfer (SURVEY.md §2.10).
    """

    def __init__(self, decode_engine):
        self.decode = decode_engine

    async def send_blocks(
        self, address: str, request_id: str, first_token: int, block_ids,
        pages,
    ) -> None:
        # address ignored: the target is in-process
        tracing.record_event_span(
            "disagg.kv_transfer",
            parent=tracing.current_span(),
            attributes={"op": "send_blocks", "path": "local",
                        "pages": len(list(block_ids)),
                        "request_id": request_id},
        )
        self.decode.complete_remote_prefill(
            request_id, first_token, list(block_ids), pages
        )

    async def send_failure(self, address: str, request_id: str, message: str) -> None:
        self.decode.fail_remote_prefill(request_id, message)

    async def read_blocks(self, address: str, block_ids) -> tuple:
        """Device path: pages come back as jax arrays, never touching host.
        Returns (pages, hashes); hashes ride along for the same staleness
        validation as the TCP path."""
        ids = list(block_ids)

        def _extract():
            return (
                self.decode.extract_blocks(ids, as_device=True),
                self.decode.block_hashes_of(ids),
            )

        return await _engine_call(self.decode, _extract)

    async def close(self) -> None:
        pass


class KvTransferClient:
    """Prefill-worker side: pooled connections to decode workers' servers.

    With a device plane, bulk KV rides the device fabric: ``send_blocks``
    stages locally + ships a pull descriptor; ``read_blocks`` asks the peer
    to stage + pulls. Peers without a plane answer ``ok=False`` and the
    call falls back to host-staged TCP — mixed fleets just work."""

    def __init__(self, device_plane=None):
        self.device_plane = device_plane
        # label the corrupt-fault gate matches on for OUTBOUND page sets:
        # defaults to the destination address; owners that model a rotten
        # SOURCE (the migration coordinator) set it to their own address so
        # a drill can corrupt one worker's sends regardless of target
        self.fault_addr = ""
        self._dev_peers: Dict[str, bool] = {}  # addr → peer has a plane
        # addr → peer's binary speaks the int8 scale layout (learned from
        # the "int8" marker new servers stamp on every reply); int8 page
        # sets avoid the device plane until proven — see send_blocks
        self._int8_peers: Dict[str, bool] = {}
        self._conns: Dict[str, tuple] = {}
        self._locks: Dict[str, asyncio.Lock] = {}

    async def _conn(self, address: str):
        c = self._conns.get(address)
        if c is None or c[1].is_closing():
            host, _, port = address.rpartition(":")
            from dynamo_tpu.runtime import faults

            reader, writer = await faults.open_connection(
                host or "127.0.0.1", int(port), plane="transfer"
            )
            c = (reader, writer)
            self._conns[address] = c
            self._locks[address] = asyncio.Lock()
        return c

    def evict(self, address: str, writer=None) -> None:
        """Drop the pooled connection to ``address`` (after a transport
        failure) so the next call dials fresh. With ``writer`` given, only
        evicts if the pool still holds *that* connection — a late-failing
        task must not close a fresh conn a concurrent task already dialed.
        The per-address lock is retained on purpose: swapping it mid-flight
        would let two tasks interleave frames on one stream."""
        c = self._conns.get(address)
        if c is None or (writer is not None and c[1] is not writer):
            return
        del self._conns[address]
        c[1].close()

    def _use_dev(self, address: str) -> bool:
        return self.device_plane is not None and self._dev_peers.get(address, True)

    def _note_caps(self, address: str, h: dict) -> None:
        if h.get("int8"):
            self._int8_peers[address] = True

    async def send_blocks(
        self,
        address: str,
        request_id: str,
        first_token: int,
        block_ids,
        pages,
    ) -> None:
        # kv_transfer span: the wire (or device-fabric) time of shipping the
        # computed pages — nests under the prefill worker's request span via
        # the ambient contextvar. The frame's header says what layout the
        # pages have, so the receiver can refuse one it doesn't speak
        # instead of writing corrupt pages.
        with tracing.span(
            "disagg.kv_transfer",
            parent=tracing.current_span(),
            phase="kv_transfer",
            attributes={"op": "send_blocks", "pages": len(list(block_ids)),
                        "address": address, "request_id": request_id},
        ) as tspan:
            # int8 pages ride the device plane only once the peer has PROVEN
            # it speaks the scale layout: a pre-int8 peer pulling a 4-array
            # stage would keep [k, v] and inject raw int8 values as native
            # KV — silent corruption. The TCP form is safe against old peers
            # (their fixed two-segment unpack fails loudly, never injects),
            # and its ack teaches us the capability for later transfers.
            if self._use_dev(address) and (
                kv_pages.is_native(pages)
                or self._int8_peers.get(address, False)
            ):
                try:
                    await self._send_blocks_dev(
                        address, request_id, first_token, block_ids, pages
                    )
                    if tspan is not None:
                        tspan.set_attribute("path", "device")
                    return
                except _NoDevicePeer:
                    self._dev_peers[address] = False  # fall through to TCP
            pages = kv_pages.to_host(pages)
            # content checksums travel with the pages (header extension;
            # receivers without the plane ignore them). Computed BEFORE the
            # corrupt-fault gate below — the drill models post-checksum
            # corruption, which is what the receiver's verify must catch.
            crcs = kv_pages.checksums(pages) if integrity.enabled() else None
            reader, writer = await self._conn(address)
            header, body = kv_pages.pack(pages, crcs)
            if _FAULTS.current() is not None:
                body = _FAULTS.corrupt_pages(
                    "transfer", self.fault_addr or address, body
                )
            if tspan is not None:
                tspan.set_attribute("path", "tcp")
                tspan.set_attribute("bytes", len(body))
            header.update({
                "op": "kv_blocks",
                "request_id": request_id,
                "first_token": int(first_token),
                "block_ids": list(map(int, block_ids)),
            })
            try:
                async with self._locks[address]:
                    await write_frame(
                        writer, TwoPartMessage(json.dumps(header).encode(), body)
                    )
                    ack = await read_frame(reader)
                ack_h = json.loads(ack.header)
                self._note_caps(address, ack_h)
            except (ConnectionError, OSError, asyncio.IncompleteReadError):
                # evict exactly the conn that failed (identity-guarded), so
                # retries dial fresh without racing concurrent senders
                self.evict(address, writer)
                raise
            if (
                ack_h.get("ok") is False
                and ack_h.get("code") == "KvIntegrityError"
            ):
                # the receiver rejected OUR pages as corrupt: the trip
                # belongs to this process (its bytes rotted after the
                # checksum) — the quarantine window hears about it
                integrity.note_trip("kv", where="kv_blocks_nack")
                raise KvIntegrityError(
                    ack_h.get("error", "peer rejected corrupt pages")
                )

    async def _send_blocks_dev(
        self, address, request_id, first_token, block_ids, pages
    ) -> None:
        import jax.numpy as jnp

        uid, specs = self.device_plane.stage(
            [jnp.asarray(a) for a in kv_pages.arrays(pages)]
        )
        try:
            reader, writer = await self._conn(address)
            header = {
                "op": "kv_blocks_dev",
                "request_id": request_id,
                "first_token": int(first_token),
                "block_ids": list(map(int, block_ids)),
                "uuid": uid,
                "specs": specs,
                "dev_addr": self.device_plane.address(),
            }
            async with self._locks[address]:
                await write_frame(
                    writer, TwoPartMessage(json.dumps(header).encode(), b"")
                )
                frame = await read_frame(reader)  # ack AFTER the peer pulled
            ack = json.loads(frame.header)
            self._note_caps(address, ack)
            if not ack.get("ok"):
                raise _NoDevicePeer()
        finally:
            self.device_plane.release(uid)

    async def read_blocks(self, address: str, block_ids) -> tuple:
        """Pull KV pages from a decode worker's pool by physical id.
        Returns (pages, hashes): the page set in the layout of the peer's
        pool (a pre-int8 peer's is native), plus each page's registered
        content hash (-1 = no longer registered). Device-path when both
        ends have a plane, host-staged TCP otherwise."""
        with tracing.span(
            "disagg.kv_transfer",
            parent=tracing.current_span(),
            phase="kv_transfer",
            attributes={"op": "read_blocks", "pages": len(list(block_ids)),
                        "address": address},
        ) as tspan:
            if self._use_dev(address):
                try:
                    out = await self._read_blocks_dev(address, block_ids)
                    if tspan is not None:
                        tspan.set_attribute("path", "device")
                    return out
                except _NoDevicePeer:
                    self._dev_peers[address] = False
            reader, writer = await self._conn(address)
            async with self._locks[address]:
                await write_frame(
                    writer,
                    TwoPartMessage(
                        json.dumps(
                            {"op": "read_blocks", "int8_ok": True,
                             "block_ids": list(map(int, block_ids))}
                        ).encode(),
                        b"",
                    ),
                )
                frame = await read_frame(reader)
            h = json.loads(frame.header)
            self._note_caps(address, h)
            if h.get("ok") is False:
                raise KvDtypeMismatch(h.get("error", "peer refused page read"))
            pages = kv_pages.unpack(h, frame.body)
            if h.get("crcs") is not None and integrity.enabled():
                # the peer's cached pages must match the checksums sealed
                # when they were computed: rot in ITS pool/wire surfaces
                # here as a typed error — callers recompute instead of
                # seeding corrupt KV into their own prefix cache
                try:
                    kv_pages.verify(pages, h["crcs"], where="read_blocks")
                except KvIntegrityError:
                    integrity.note_remote_failure("read_blocks")
                    raise
            if tspan is not None:
                tspan.set_attribute("path", "tcp")
                tspan.set_attribute("bytes", len(frame.body))
            return pages, h.get("hashes") or [-1] * kv_pages.count(pages)

    async def _read_blocks_dev(self, address: str, block_ids) -> tuple:
        reader, writer = await self._conn(address)
        async with self._locks[address]:
            await write_frame(
                writer,
                TwoPartMessage(
                    json.dumps(
                        {"op": "read_blocks_dev", "int8_ok": True,
                         "block_ids": list(map(int, block_ids))}
                    ).encode(),
                    b"",
                ),
            )
            frame = await read_frame(reader)
        h = json.loads(frame.header)
        self._note_caps(address, h)
        if not h.get("ok"):
            raise _NoDevicePeer()
        try:
            pulled = await asyncio.to_thread(
                self.device_plane.pull, h["dev_addr"], h["uuid"], h["specs"]
            )
        finally:
            # tell the peer to drop its staged copy (success or failure —
            # a failed pull must not pin its HBM pages until the TTL)
            async with self._locks[address]:
                await write_frame(writer, TwoPartMessage(
                    json.dumps({"op": "release_dev", "uuid": h["uuid"]}).encode(),
                    b"",
                ))
                await read_frame(reader)
        return (
            kv_pages.from_arrays(pulled),
            h.get("hashes") or [-1] * len(block_ids),
        )

    async def send_failure(self, address: str, request_id: str, message: str) -> None:
        reader, writer = await self._conn(address)
        async with self._locks[address]:
            await write_frame(
                writer,
                TwoPartMessage(
                    json.dumps(
                        {"op": "prefill_failed", "request_id": request_id, "message": message}
                    ).encode(),
                    b"",
                ),
            )
            await read_frame(reader)

    async def migrate(self, address: str, meta: dict, pages) -> dict:
        """Ship one live-migrating stream's checkpoint + history pages to
        ``address`` atomically (docs/resilience.md §Live migration). The
        target stages the pages ahead of the re-homed client's admission;
        a typed rejection (OOM, dtype/block-size skew) raises
        :class:`MigrationRejected` / :class:`KvDtypeMismatch`, transport
        failures raise as usual — the caller degrades the stream to the
        resume path in every failure case. Returns the ack's ``staged``
        summary."""
        pages = kv_pages.to_host(pages)
        with tracing.span(
            "disagg.kv_transfer",
            parent=tracing.current_span(),
            phase="kv_transfer",
            attributes={"op": "migrate", "pages": kv_pages.count(pages),
                        "address": address,
                        "request_id": meta.get("request_id", "")},
        ) as tspan:
            reader, writer = await self._conn(address)
            # meta may carry per-block "crcs" (the coordinator's seal-time
            # checksums); the corrupt-fault gate below models a source
            # whose bytes rot AFTER checksumming — the target's staging
            # verify must nack it
            header, body = kv_pages.pack(pages)
            if _FAULTS.current() is not None:
                body = _FAULTS.corrupt_pages(
                    "transfer", self.fault_addr or address, body
                )
            header.update({"op": "migrate", "migrate": meta})
            if tspan is not None:
                tspan.set_attribute("path", "tcp")
                tspan.set_attribute("bytes", len(body))
            try:
                async with self._locks[address]:
                    await write_frame(
                        writer,
                        TwoPartMessage(json.dumps(header).encode(), body),
                    )
                    frame = await read_frame(reader)
            except asyncio.CancelledError:
                # the caller's migrate timeout fired mid-protocol (possibly
                # mid-frame): the connection's request/ack pairing can no
                # longer be trusted — a later migrate on it would read THIS
                # stream's stale ack and mis-credit its outcome. Evict so
                # the next ship dials fresh.
                self.evict(address, writer)
                raise
            except (ConnectionError, OSError, asyncio.IncompleteReadError):
                self.evict(address, writer)
                raise
            ack = json.loads(frame.header)
            self._note_caps(address, ack)
            if not ack.get("ok"):
                code = ack.get("code", "")
                msg = ack.get("error", "peer refused migration")
                if code == "KvDtypeMismatch":
                    raise KvDtypeMismatch(msg)
                if code == "KvIntegrityError":
                    raise KvIntegrityError(msg)
                raise MigrationRejected(msg)
            return ack.get("staged") or {}

    async def close(self) -> None:
        for _, w in self._conns.values():
            w.close()
