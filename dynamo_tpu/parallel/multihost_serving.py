"""Multi-host serving: one engine, a process-spanning mesh, lockstep SPMD.

The serving engine is a single-controller design (one host owns admission,
the allocator and streaming), but a model sharded over a MULTI-PROCESS mesh
requires every process to execute the same XLA program in the same order.
This module closes that gap with a lockstep protocol:

- the **leader** (process 0) runs the full engine; its ``_dispatch_hook``
  broadcasts a descriptor of every jitted dispatch — opcode, variant flags,
  and the host input arrays — via ``multihost_utils.broadcast_one_to_all``;
- every **follower** runs :func:`follower_serve`: it builds the same engine
  object (same params, same mesh, no step thread), receives descriptors,
  and invokes the identical jitted fns with identical replicated inputs —
  its shards participate in the program's collectives over ICI/DCN.

Reference parity: MultiNodeConfig engines (lib/llm/src/engines.rs:41-59) and
the vLLM0.7 Ray leader/follower bring-up (lib/engines/vllm0_7/src/ray.rs:
66-170) — re-designed for XLA's SPMD model: instead of an engine-internal
NCCL world driven by RPC, the *dispatch stream itself* is the coordination
channel, and XLA inserts the cross-host collectives.

The full sampling surface rides the descriptors (reference parity:
multinode engines serve logprobs/penalties like any other request,
lib/engines/vllm0_7/src/ray.rs:66-170): ``lp``/``pen`` variant bits select
the same jitted fn on both sides, and the penalty-count sync — itself a
device program — is broadcast as its own opcode so followers execute the
identical program sequence.
"""

from __future__ import annotations

import logging
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

# opcodes on the broadcast channel
OP_SHUTDOWN = 0
OP_CHUNK = 1
OP_DECODE = 2
OP_COUNTS = 3  # penalty-count row sync (reset + rebuild scatters)
OP_COUNTS_RELEASE = 4  # idle engine dropped the count buffer: followers too

_HDR = 8  # int32 header slots


def _broadcast(tree):
    from jax.experimental import multihost_utils as mhu

    return mhu.broadcast_one_to_all(tree)


class LeaderBroadcaster:
    """The engine-side dispatch hook: ships each dispatch to the followers.

    Install with ``engine._dispatch_hook = LeaderBroadcaster(engine)``; call
    :meth:`shutdown` when serving ends so followers exit their loop."""

    def __init__(self, engine):
        self.engine = engine
        self._ec = engine.config

    def __call__(self, kind: str, flags: dict, arrays: dict) -> None:
        hdr = np.zeros((_HDR,), np.int32)
        if kind == "counts_release":
            hdr[0] = OP_COUNTS_RELEASE
            _broadcast(hdr)
            return
        if kind == "counts":
            # variable-size scatter payload: sizes ride the header
            hdr[0] = OP_COUNTS
            hdr[1] = int(flags["rb"])
            hdr[2] = int(flags["pb"])
            _broadcast(hdr)
            _broadcast((
                arrays["reset"].astype(np.int32),
                arrays["add_rows"].astype(np.int32),
                arrays["add_toks"].astype(np.int32),
            ))
            return
        hdr[0] = OP_CHUNK if kind == "chunk" else OP_DECODE
        hdr[1] = int(flags.get("sample", False))
        hdr[2] = int(flags.get("history", True))
        hdr[3] = int(flags.get("use_carry", False))
        hdr[4] = int(flags["step"])
        hdr[5] = int(flags.get("lp", False))
        hdr[6] = int(flags.get("pen", False))
        _broadcast(hdr)
        if kind == "chunk":
            payload = (
                arrays["tokens"].astype(np.int32),
                arrays["positions"].astype(np.int32),
                arrays["tables"].astype(np.int32),
                arrays["sample_at"].astype(np.int32),
                arrays["lanes"].astype(np.int32),
                arrays["ipack"].astype(np.int32),
                arrays["fpack"].astype(np.float32),
            )
        else:
            payload = (
                arrays["tokens"].astype(np.int32),
                arrays["positions"].astype(np.int32),
                arrays["tables"].astype(np.int32),
                arrays["ipack"].astype(np.int32),
                arrays["fpack"].astype(np.float32),
            )
        _broadcast(payload)

    def shutdown(self) -> None:
        hdr = np.zeros((_HDR,), np.int32)
        hdr[0] = OP_SHUTDOWN
        _broadcast(hdr)


def follower_serve(model_config, params, engine_config, mesh, engine=None) -> None:
    """Run a follower: execute the leader's dispatch stream until shutdown.

    Must be called with the SAME model config, params and engine config the
    leader built its engine from, on every non-zero process of the
    ``jax.distributed`` world, after the global mesh exists. Pass ``engine``
    when an (already-warmed) engine exists — the CLI builds + warms one on
    every rank so the warmup dispatches themselves run in lockstep."""
    from dynamo_tpu.engine_jax.engine import JaxServingEngine

    if engine is not None:
        eng = engine
    else:
        eng = JaxServingEngine(model_config, params, engine_config, mesh=mesh)
        # warmup is itself a sequence of global dispatches: the leader runs
        # the same calls before serving (contract: leader warms up, THEN
        # installs the broadcast hook), so both sides run it in lockstep here
        eng.warmup()
    S, C = engine_config.max_slots, engine_config.prefill_chunk
    MB = engine_config.max_blocks_per_seq
    carry = None  # (tokens, positions) device arrays from the last decode
    counts = eng._dummy_counts
    z_i = np.zeros((S,), np.int32)

    logger.info("multihost follower serving (process %d)", _process_index())
    while True:
        hdr = _broadcast(np.zeros((_HDR,), np.int32))
        op = int(hdr[0])
        if op == OP_SHUTDOWN:
            logger.info("follower shutdown")
            return
        if op == OP_COUNTS_RELEASE:
            eng._counts = None
            continue
        if op == OP_COUNTS:
            rb, pb = int(hdr[1]), int(hdr[2])
            reset, add_rows, add_toks = _broadcast((
                np.zeros((rb,), np.int32), np.zeros((pb,), np.int32),
                np.zeros((pb,), np.int32),
            ))
            if eng._counts is None:
                eng._counts = eng._put(
                    np.zeros((S, model_config.vocab_size), np.int32)
                )
            eng._counts = eng._counts_sync_fn(rb, pb)(
                eng._counts, eng._put(reset), eng._put(add_rows),
                eng._put(add_toks),
            )
            continue
        want_sample = bool(hdr[1])
        want_history = bool(hdr[2])
        use_carry = bool(hdr[3])
        step = int(hdr[4])
        want_lp = bool(hdr[5])
        want_pen = bool(hdr[6])
        counts_in = eng._counts if want_pen else counts
        if op == OP_CHUNK:
            # the leader packs one row per prefilling lane; an engine on a
            # process-spanning mesh has the one-rung ladder, so the packed
            # rows arrive at the fixed [S, C] shape (engine._chunk_rungs)
            tokens, positions, tables, sample_at, lanes, ipack, fpack = _broadcast((
                np.zeros((S, C), np.int32), np.zeros((S, C), np.int32),
                np.zeros((S, MB), np.int32), z_i, z_i,
                np.zeros((2, S), np.int32), np.zeros((4, S), np.float32),
            ))
            fn = eng._chunk(want_lp, want_pen, want_sample, want_history)
            res = fn(
                eng.params, eng.cache, counts_in, eng._put(tokens),
                eng._put(positions), eng._put(tables),
                eng._put(sample_at), eng._put(lanes),
                eng._put(np.int32(step)), eng._put(ipack), eng._put(fpack),
            )
            # lp variants return (sampled, lp, ids, lps, cache, counts)
            eng.cache, counts_out = res[-2], res[-1]
            # the decode carry stays: the leader dispatches its decode program
            # beside a chunk off the same carry (use_carry rides the header)
        else:
            tokens, positions, tables, ipack, fpack = _broadcast((
                z_i, z_i, np.zeros((S, MB), np.int32),
                np.zeros((2, S), np.int32), np.zeros((4, S), np.float32),
            ))
            if use_carry and carry is not None:
                toks_in, pos_in = carry
            else:
                toks_in, pos_in = eng._put(tokens), eng._put(positions)
            fn = eng._decode(want_lp, want_pen, want_sample)
            res = fn(
                eng.params_decode, eng.cache, counts_in, toks_in, pos_in,
                eng._m_tables.get(tables), eng._put(np.int32(step)),
                eng._m_ipack.get(ipack), eng._m_fpack.get(fpack),
            )
            # (out[, lps, ids, lps], tokens, positions, cache, counts)
            eng.cache, counts_out = res[-2], res[-1]
            carry = (res[-4], res[-3])
        # mirror the leader's counts bookkeeping: penalized dispatches carry
        # the real buffer forward, others the dummy; the buffer is dropped
        # when the leader says so (OP_COUNTS_RELEASE), which it does after a
        # dispatch that penalized nothing unless another lane still needs it
        if want_pen:
            eng._counts = counts_out
        else:
            counts = counts_out


def _process_index() -> int:
    import jax

    return jax.process_index()


def shard_params_global(params, model_config, mesh):
    """Shard a (host-replicated) param pytree over a process-spanning mesh.

    Every process holds the same full host values (e.g. identical
    init/checkpoint load); each materializes only its device shards via
    ``make_array_from_callback``. Works for single-process meshes too."""
    import jax

    from dynamo_tpu.models.llama import param_shardings

    sh = param_shardings(model_config, mesh)

    def put(leaf, sharding):
        a = np.asarray(leaf)
        return jax.make_array_from_callback(
            a.shape, sharding, lambda idx: a[idx]
        )

    return jax.tree.map(put, params, sh)
