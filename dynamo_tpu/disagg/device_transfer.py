"""Cross-host device-path KV transfer.

The host-staged TCP plane (disagg/transfer.py) works everywhere but pays
device→host→TCP→host→device. On platforms whose PJRT backend implements the
transfer-server API (``jax.experimental.transfer`` — TPU pods; the CPU
backend does not), KV pages move DEVICE-to-device: the owner stages arrays
under a uuid on its transfer server, the peer pulls them straight into its
own HBM over the accelerator fabric / DCN, the way the reference moves
VRAM→VRAM via NIXL RDMA (vllm patch nixl.py read_blocks/write_blocks,
SURVEY.md §2.10).

Split of responsibilities:
- control stays on the existing framed-TCP channel (tiny messages: which
  blocks, which uuid, hash validation);
- bulk rides the device plane.

Capability is probed once at startup; everything degrades to the host-staged
path when the backend (or the peer) lacks support, so deployments mix
freely.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

_STAGE_TTL_S = 180.0  # staged-but-never-pulled entries drop after this


_supported: Optional[bool] = None


def device_transfer_supported() -> bool:
    """Can this process host/pull device-path transfers? Probed once.

    Platform-gated to TPU: the CPU backend passes a same-process self-pull
    (it shortcuts the staging path) but lacks the cross-process PJRT hooks
    (``PJRT_Client_CreateBuffersForAsyncHostToDevice``), so a probe alone
    would report a capability that breaks on the first real peer."""
    global _supported
    if _supported is None:
        import jax

        platform = jax.devices()[0].platform
        if platform != "tpu":
            logger.info(
                "device-path KV transfer: platform %r lacks cross-process "
                "PJRT transfer hooks; using the host-staged path", platform,
            )
            _supported = False
            return False
        try:
            from jax.experimental import transfer

            s = transfer.start_transfer_server(jax.devices()[0].client)
            _probe_roundtrip(s)
            _supported = True
        except Exception as e:
            # the probe failing ON A TPU means KV moves host-staged on
            # hardware built for the device path — say so, with the cause
            logger.warning(
                "device-path KV transfer probe failed on a TPU; using the "
                "host-staged path: %r", e,
            )
            _supported = False
    return _supported


def _probe_roundtrip(server) -> None:
    """Self-connect and pull one tiny array — exercises the client hooks
    (CreateBuffersForAsyncHostToDevice) that some backends lack even when
    the server starts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    x = jnp.arange(4, dtype=jnp.float32)
    server.await_pull(0, [x])
    conn = server.connect(server.address())
    spec = jax.ShapeDtypeStruct(
        (4,), jnp.float32, sharding=SingleDeviceSharding(jax.devices()[0])
    )
    out = conn.pull(0, [spec])
    if float(out[0][0]) != 0.0:
        raise RuntimeError("device transfer probe returned wrong data")


class DevicePlane:
    """One process's staging/pull endpoint for device-path KV movement."""

    def __init__(self):
        import jax
        from jax.experimental import transfer

        self._server = transfer.start_transfer_server(jax.devices()[0].client)
        self._conns: Dict[str, Any] = {}
        self._uuid = itertools.count(1)
        self._staged: Dict[int, Tuple[float, list]] = {}  # uuid → (t, arrays)
        self._lock = threading.Lock()

    def address(self) -> str:
        return self._server.address()

    def stage(self, arrays: List[Any]) -> Tuple[int, List[dict]]:
        """Register device arrays for one pull; returns (uuid, specs)."""
        uid = next(self._uuid)
        self._server.await_pull(uid, list(arrays))
        specs = [
            {"shape": list(a.shape), "dtype": str(a.dtype)} for a in arrays
        ]
        with self._lock:
            now = time.monotonic()
            self._staged[uid] = (now, list(arrays))  # keep alive until pulled
            for k, (t, _) in list(self._staged.items()):
                if now - t > _STAGE_TTL_S:
                    del self._staged[k]
        return uid, specs

    def release(self, uid: int) -> None:
        with self._lock:
            self._staged.pop(uid, None)

    def pull(self, address: str, uid: int, specs: List[dict]) -> list:
        """Pull staged arrays from a peer plane into local device memory."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        conn = self._conns.get(address)
        if conn is None:
            conn = self._conns[address] = self._server.connect(address)
        dev = jax.devices()[0]
        sds = [
            jax.ShapeDtypeStruct(
                tuple(s["shape"]), jnp.dtype(s["dtype"]),
                sharding=SingleDeviceSharding(dev),
            )
            for s in specs
        ]
        return conn.pull(uid, sds)


def make_device_plane() -> Optional[DevicePlane]:
    """A DevicePlane when the backend supports it, else None (callers fall
    back to the host-staged TCP path)."""
    if not device_transfer_supported():
        return None
    try:
        return DevicePlane()
    except Exception:
        logger.exception("device plane construction failed; using host path")
        return None
