"""KV transfer microbench: device plane vs host-staged, per block count.

Part of the staged first real multi-chip session
(docs/multihost_serving.md): run on ≥2 real chips with
``DYN_TPU_TESTS_REAL=1 python tools/bench_transfer.py``. On one chip (or
CPU) it still runs the host-staged plane so the harness itself stays
exercised. Prints one JSON line per configuration.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = (16, 8, 64)  # tokens × kv heads × head dim (tiny-model geometry)
LAYERS = 16


def bench_device_plane(n_blocks: int) -> dict:
    from dynamo_tpu.disagg.device_transfer import (
        DevicePlane,
        device_transfer_supported,
    )

    if not device_transfer_supported():
        return {"plane": "device", "supported": False}
    plane = DevicePlane()
    devs = [d for d in jax.devices() if d.platform == "tpu"] or jax.devices()
    src = devs[0]
    arrays = [
        jax.device_put(
            jnp.ones((n_blocks,) + BLOCK, jnp.bfloat16) * (i + 1), src
        )
        for i in range(LAYERS)
    ]
    jax.block_until_ready(arrays)
    nbytes = sum(a.nbytes for a in arrays)
    t0 = time.perf_counter()
    uid, specs = plane.stage(arrays)
    out = plane.pull(plane.address(), uid, specs)
    jax.block_until_ready(out)
    _ = np.asarray(out[0][0])  # a host fetch: the copy has certainly landed
    dt = time.perf_counter() - t0
    return {
        "plane": "device", "supported": True, "blocks": n_blocks,
        "bytes": nbytes, "ms": round(dt * 1e3, 2),
        "gbps": round(nbytes / dt / 1e9, 3),
    }


def bench_host_staged(n_blocks: int) -> dict:
    """The fallback path: device→host fetch + host→device put (the TCP hop
    between processes is benched by the disagg e2e; this isolates the two
    staging copies that bound it)."""
    devs = jax.devices()
    arrays = [
        jnp.ones((n_blocks,) + BLOCK, jnp.bfloat16) * (i + 1)
        for i in range(LAYERS)
    ]
    jax.block_until_ready(arrays)
    nbytes = sum(a.nbytes for a in arrays)
    t0 = time.perf_counter()
    host = [np.asarray(a) for a in arrays]
    back = [jax.device_put(h, devs[-1]) for h in host]
    jax.block_until_ready(back)
    _ = np.asarray(back[0][0])
    dt = time.perf_counter() - t0
    return {
        "plane": "host-staged", "blocks": n_blocks, "bytes": nbytes,
        "ms": round(dt * 1e3, 2), "gbps": round(nbytes / dt / 1e9, 3),
    }


def main():
    for n_blocks in (1, 8, 64):
        print(json.dumps(bench_device_plane(n_blocks)), flush=True)
        print(json.dumps(bench_host_staged(n_blocks)), flush=True)


if __name__ == "__main__":
    main()
