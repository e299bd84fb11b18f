"""The continuous-batching JAX serving engine.

Architecture (TPU-first, cf. SURVEY.md §7 stage 4):

- **Fixed batch slots**: `max_slots` decode lanes; a request occupies one slot
  from first token to finish. All decode steps run ONE jitted function with
  static shapes — no recompilation, ever.
- **Chunked, batched prefill**: a host step with a prefilling lane runs ONE
  compiled `[rows, prefill_chunk]` function that holds the rows of the lanes
  that prefill and nothing else: `rows` is a rung of a short ladder
  (`chunk_row_ladder`: `max_slots` and at most two rungs under it), so the
  dispatch costs what its prefilling lanes need. A lane takes as many rows
  as its prompt needs while a rung under `max_slots` holds them
  (`chunk_rows_of`: a prompt prefills in one host step, a later piece
  attending the earlier rows' fresh keys inside the program), where the
  model's module allows it; otherwise one row a lane. At `max_slots` rows,
  which only the number of prefilling lanes forces, the rows left over go to
  further pieces too where the module's full-width program reads the rows'
  lanes (`FULL_WIDTH_TAKES_ROWS`); elsewhere they are padding. The lanes that decode
  are never rows of it: they advance through the decode program in the same
  host step, and both programs are dispatched before either result is
  fetched. (On a process-spanning mesh, under pp and under sp the decode
  lanes still ride the chunk dispatch at the full width, one token each, and
  a lane has one row: `_rides`, which says why.) What a dispatch does not
  hold of a prompt goes on in the next step (long-context prefill is chunked
  by construction; no shape depends on prompt length).
- **Paged KV**: allocator (allocator.py) maps sequences onto a page pool in
  HBM with content-addressed prefix reuse; the model writes-then-attends
  through block tables (models/llama.py), making prefix hits free.
- **In-jit sampling** (sampling.py): only token ids cross to host per step.
- **Step loop on a dedicated thread**: jax dispatch blocks, asyncio must not.
  Tokens stream to requesters via `loop.call_soon_threadsafe` into per-request
  asyncio queues — this is how tokens cross the jit/async boundary.

The engine implements the framework AsyncEngine interface (token-in/token-out,
like the reference's ExecutionContext engines, SURVEY.md §2.5) so it slots into
the same pipelines as the echo engines and remote clients.
"""

from __future__ import annotations

import asyncio
import logging
import math
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine_jax.allocator import (
    BlockAllocator,
    HostKvPool,
    InflightPrefix,
    KvEventSink,
    SequenceAllocation,
)
from dynamo_tpu.engine_jax.drafter import (
    MAX_SPEC_K,
    DeviceDrafter,
    NgramDrafter,
    env_kv_dtype,
    env_spec_k,
    env_spec_ngram,
)
from dynamo_tpu.engine_jax.sampling import (
    apply_penalties,
    sample_tokens,
    speculative_targets,
    token_logprobs,
    update_counts,
)
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
)
from dynamo_tpu.models import module_for
from dynamo_tpu.models.llama import (
    LlamaConfig,
    dequantize_kv,
    flush_window,
    forward,
    forward_chunk,
    forward_window,
    gather_history,
    history_tiles_full,
    lm_head,
    make_kv_cache,
    quantize_kv,
    with_live_history,
)
from dynamo_tpu.engine_jax.compile_cache import compile_count, record_compile
from dynamo_tpu.engine_jax.seal_crc import SealCrcWorker
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.kv.pages import KvDtypeMismatch, MigrationRejected
from dynamo_tpu.runtime import faults as faults_mod
from dynamo_tpu.runtime import integrity as integrity_mod
from dynamo_tpu.runtime import profiling as profiling_mod
from dynamo_tpu.runtime.profiling import (
    P_ADMIT, P_ALLOC, P_CHUNK_BUILD, P_CHUNK_DISPATCH, P_CHUNK_EMIT,
    P_CHUNK_FETCH, P_COMPILE, P_DECODE_BUILD, P_DECODE_DISPATCH,
    P_DECODE_EMIT, P_DECODE_FETCH, P_DRAIN, P_POSTED, P_PREPARE, P_SEAL_CRC,
    P_SEAL_READ, P_SPILLS, P_SWEEP, P_WAIT,
)
from dynamo_tpu.runtime import qos as qos_mod
from dynamo_tpu.runtime import straggler as straggler_mod
from dynamo_tpu.runtime import telemetry, tracing
from dynamo_tpu.runtime.integrity import WATCHDOG_TOKEN
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.health import EngineHeartbeat

logger = logging.getLogger(__name__)


class _EnginePerf:
    """Live decode-perf accounting (engine thread only, EMA-smoothed).

    The benchmark reduces tokens/s and roofline shares from a run *after* it
    (``benchmark/run.py``); this keeps their host-clock estimates as live gauges on the metrics stream
    (``ForwardPassMetrics.decode_tokens_per_s`` etc.) so the telemetry
    plane — and eventually the SLA planner — can see a decode regression as
    it happens. Built only when telemetry sampling is enabled
    (``DYN_TPU_SLO=0`` ⇒ the engine holds ``None`` and the step loop pays
    one attribute check, asserted by ``tests/test_telemetry.py``).

    Timing anchors on the gap between consecutive *processed* decode chunks
    (which in pipelined decode equals the chunk's wall time); idle gaps are
    excluded via :meth:`note_idle` so a quiet engine's throughput gauge
    reflects its last busy period instead of decaying toward zero.
    """

    __slots__ = (
        "decode_tps", "step_time_ms", "slot_util", "spec_accept_rate",
        "_last_t", "_alpha",
    )

    def __init__(self, alpha: float = 0.2):
        self.decode_tps = 0.0
        self.step_time_ms = 0.0
        self.slot_util = 0.0
        # acceptance-rate EMA over verify dispatches (accepted drafts /
        # drafted); 0.0 with speculation off or before the first draft
        self.spec_accept_rate = 0.0
        self._last_t: Optional[float] = None
        self._alpha = alpha

    def _ema(self, prev: float, sample: float) -> float:
        return sample if prev == 0.0 else prev + self._alpha * (sample - prev)

    def note_decode(self, n_tokens: int, k_steps: int) -> None:
        now = time.perf_counter()
        last, self._last_t = self._last_t, now
        if last is None:
            return
        dt = now - last
        if dt <= 0:
            return
        if n_tokens > 0:
            self.decode_tps = self._ema(self.decode_tps, n_tokens / dt)
        self.step_time_ms = self._ema(
            self.step_time_ms, dt * 1e3 / max(k_steps, 1)
        )

    def note_slots(self, active: int, total: int) -> None:
        if total > 0:
            self.slot_util = self._ema(self.slot_util, active / total)

    def note_spec(self, drafted: int, accepted: int) -> None:
        if drafted > 0:
            self.spec_accept_rate = self._ema(
                self.spec_accept_rate, accepted / drafted
            )

    def note_idle(self) -> None:
        self._last_t = None


@dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8
    kv_block_size: int = 16
    max_model_len: int = 2048
    num_kv_blocks: Optional[int] = None  # default: 1.5× what max_slots need
    # tokens of prompt consumed per prefilling lane per step — the unit of
    # prefill/decode interleaving (a decode lane is delayed at most one
    # chunk's compute by any admission wave)
    prefill_chunk: int = 128
    # decode steps per device dispatch: each dispatch scans this many
    # forward+sample steps in one jitted call, amortizing the per-dispatch
    # host cost (launch, result fetch, lane bookkeeping) and the once-per-
    # dispatch history gather / window flush over several tokens. On a local
    # chip a blocking dispatch costs about a millisecond of host time over
    # its device time (chip_smoke.py host-clock-vs-trace phase); what the
    # best value is per cell is not measured on the current machine. Tokens
    # past a stop condition are discarded host-side; worst case wastes
    # decode_steps-1 token computations per finished request.
    decode_steps: int = 1
    # safety net for disaggregated prefill: a sequence whose remote prefill
    # hasn't landed within this window falls back to local prefill
    remote_prefill_timeout: float = 60.0
    # host-RAM KV tier: evicted device blocks spill here and re-enter HBM on
    # a prefix hit (0 = disabled). Sized in blocks; reference credits the
    # equivalent pinned-host tier with +40% TTFT on multi-turn (SURVEY.md).
    host_cache_blocks: int = 0
    # alternatives computed per step for OpenAI logprobs; matches OpenAI's
    # documented top_logprobs bound so a validated request is never silently
    # truncated. Computed (and transferred) only when a request asks.
    top_logprobs: int = 20
    # admission-wave coalescing: when the engine is idle and requests are
    # still arriving, wait up to this long (seconds) for the wave to finish
    # landing so every prompt prefills in ONE chunk dispatch instead of the
    # stragglers eating a whole extra chunk of TTFT. A lone request pays at
    # most one poll interval (~3 ms); an idle engine with a full wave pays
    # nothing extra at all (the wave fills the slots and the wait ends).
    admission_window: float = 0.02
    # budget for the dense decode-history buffer ([L, S, max_model_len] K+V,
    # gathered once per decode dispatch). Under it: dense windowed decode
    # (faster — measured ~1.4x over paged DMA at 2k ctx on v5e). Over it:
    # the Pallas kernel streams live pages from HBM with zero extra
    # residency (the 70B/long-context regime). DYN_TPU_ATTENTION overrides.
    dense_history_max_bytes: int = 2 << 30
    # weight-only quantization: "int8" halves the decode weight stream
    # (per-output-channel absmax, models/llama.py quantize_params_int8).
    # Single-chip path; mesh-sharded configs keep bf16.
    quantize: Optional[str] = None
    # self-draft speculative decoding: number of n-gram-drafted tokens
    # verified per decode dispatch (engine_jax/drafter.py). None = read
    # DYN_TPU_SPEC_K (default 0 = off); values clamp to [0, MAX_SPEC_K].
    # Every accepted draft amortizes one full decode weight stream.
    spec_k: Optional[int] = None
    # longest trailing n-gram the drafter probes (None = DYN_TPU_SPEC_NGRAM,
    # default 3)
    spec_ngram: Optional[int] = None
    # multi-tenant QoS (runtime/qos.py): prefill duty-cycle budget — the
    # AVERAGE prefill tokens allowed per host step while decode lanes
    # are live: one step with a chunk dispatch, then ~chunk/budget steps
    # of the decode program alone. Long prompts raise their OWN TTFT
    # instead of every decode lane's ITL; an engine with no decode lanes
    # prefills at full speed. (Written when a chunk dispatch cost full
    # [S, C] compute and carried decode lanes one token; it now costs its
    # prefilling rows and they run beside it: ROADMAP D6.) None = read
    # DYN_TPU_PREFILL_BUDGET (clamped; default 0 = unlimited, the pre-QoS
    # behavior).
    prefill_budget: Optional[int] = None
    # KV page storage dtype: "bf16" (native — actually the cache_dtype /
    # model dtype) or "int8" (quantized pages + per-block scale tables,
    # halving the KV half of the decode stream at long context). None =
    # read DYN_TPU_KV_DTYPE. int8 KV is single-chip (mesh=None) for now and
    # pins the dense decode-history tier (the Pallas kernel has no fused
    # dequant yet — ROADMAP item 2 pairs them).
    kv_dtype: Optional[str] = None

    def resolve_num_blocks(self) -> int:
        if self.num_kv_blocks is not None:
            return self.num_kv_blocks
        per_seq = math.ceil(self.max_model_len / self.kv_block_size)
        return int(self.max_slots * per_seq * 3 // 2)

    @property
    def max_blocks_per_seq(self) -> int:
        return math.ceil(self.max_model_len / self.kv_block_size)


class _Seq:
    """One in-flight request's host-side state."""

    __slots__ = (
        "ctx", "request", "prompt", "alloc", "slot", "out_queue", "loop",
        "generated", "emitted", "max_tokens", "eos_ids", "ignore_eos",
        "temperature", "top_k", "top_p", "seed", "logprobs", "enqueue_t",
        "first_token_t", "admit_t", "remote", "remote_deadline", "prefill_pos",
        "freq_pen", "pres_pen", "out_tokens", "joined_inflight", "wait_hash",
        "drafter", "spec_drafted", "spec_accepted", "tenant", "level",
        "weight", "resumed", "migrated", "prefix_declined",
    )

    def __init__(self, ctx: Context, request: PreprocessedRequest, loop) -> None:
        self.ctx = ctx
        self.request = request
        self.prompt: List[int] = list(request.token_ids)
        self.alloc: Optional[SequenceAllocation] = None
        self.slot: Optional[int] = None
        self.out_queue: asyncio.Queue = asyncio.Queue()
        self.loop = loop
        self.generated: List[int] = []
        # tokens streamed to the caller — survives preemption (generated is
        # absorbed into prompt on preempt, so it can't back max_tokens)
        self.emitted = 0
        sc = request.stop_conditions
        self.max_tokens = sc.max_tokens if sc.max_tokens is not None else 2**30
        self.eos_ids: Set[int] = set(request.eos_token_ids or [])
        self.ignore_eos = bool(sc.ignore_eos)
        so = request.sampling_options
        self.temperature = so.temperature if so.temperature is not None else 0.0
        self.top_k = so.top_k if so.top_k is not None else 0
        self.top_p = so.top_p if so.top_p is not None else 1.0
        self.seed = so.seed if so.seed is not None else 0
        self.freq_pen = so.frequency_penalty or 0.0
        self.pres_pen = so.presence_penalty or 0.0
        # all output tokens ever emitted — unlike `generated`, survives
        # preemption; rebuilds the device penalty-count row on re-admission
        self.out_tokens: List[int] = []
        # mid-stream resume (runtime/resilience.StreamJournal wire marker):
        # token_ids[prompt_len:] are ANOTHER worker's already-emitted output
        # riding in as prompt. Pre-seeding out_tokens hands them to the same
        # _sync_counts rebuild that preemption uses, so frequency/presence
        # penalties continue exactly where the dead stream left off —
        # identical machinery, zero new device code. Positions/KV treat the
        # full token_ids as prompt (that IS the recompute; the prefix cache
        # and host tier soften it like any preemption recompute).
        self.resumed = False
        # live migration (disagg/migration.py): set at admission when this
        # request adopted a staged migration's allocation — its "prefill"
        # is one fresh position, not a recompute
        self.migrated = False
        res = getattr(request, "resume", None)
        if isinstance(res, dict):
            try:
                plen = int(res.get("prompt_len", 0))
            except (TypeError, ValueError):
                plen = 0
            if 0 < plen <= len(self.prompt):
                self.resumed = True
                self.out_tokens = list(self.prompt[plen:])
        # None = don't emit logprobs; 0 = chosen only; k = with alternatives
        self.logprobs = so.logprobs
        self.enqueue_t = time.perf_counter()
        self.first_token_t: Optional[float] = None
        # first slot admission (tracing: queue_wait ends, prefill begins);
        # preemption re-admissions keep the original stamp
        self.admit_t: Optional[float] = None
        self.remote = False  # prefill dispatched to a remote prefill worker
        self.remote_deadline: Optional[float] = None
        self.joined_inflight = False  # parked behind a concurrent identical prefix
        self.prefix_declined = 0  # cached prompt tokens passed over (a slot model)
        self.wait_hash: Optional[int] = None  # the in-flight hash it's parked on
        # next prompt position to compute while prefilling; None = decoding
        self.prefill_pos: Optional[int] = None
        # self-draft speculation (engine_jax/drafter.py): the engine attaches
        # a per-sequence NgramDrafter only when spec_k > 0 — None keeps the
        # spec-off step loop allocation-free (the same None-check pattern as
        # _EnginePerf). Counters feed the per-request acceptance attributes
        # on the engine.decode span and the spec_accept phase histogram.
        self.drafter = None
        self.spec_drafted = 0
        self.spec_accepted = 0
        # multi-tenant QoS (runtime/qos.py): tenant id + class level/weight
        # stamped by generate() when QoS is on (or a bare tenant id for
        # attribution when off). Defaults keep the single-tenant step loop
        # on the zero-bookkeeping path.
        self.tenant = ""
        self.level = 0
        self.weight = 1.0

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def penalized(self) -> bool:
        return self.freq_pen != 0.0 or self.pres_pen != 0.0

    def emit(self, item) -> None:
        # The consumer's event loop can die under us (client teardown, a
        # finished asyncio.run) while the engine is still processing this
        # sequence's speculative chunk. Emitting into a dead loop can wedge
        # the ENGINE THREAD in call_soon_threadsafe's self-pipe write —
        # observed as permanently leaked blocks + a stuck step loop. Nobody
        # can receive these items; drop them.
        if self.loop.is_closed():
            return
        try:
            self.loop.call_soon_threadsafe(self.out_queue.put_nowait, item)
        except RuntimeError:
            pass  # loop closed between the check and the call


_FINISHED = object()  # sentinel closing a request's output queue


class _DevMirror:
    """Host→device upload cache: re-uploads only when the host array changed.

    Every `jnp.asarray` is a separate host→device transfer issued from the
    step loop; the sampling vectors change only on lane changes, so in
    steady-state decode they hit this cache every dispatch. What a transfer
    costs the step loop is not measured on the current machine."""

    __slots__ = ("_host", "_dev", "_put")

    def __init__(self, put=None):
        self._host: Optional[np.ndarray] = None
        self._dev = None
        self._put = put or jnp.asarray

    def get(self, host_arr: np.ndarray):
        if self._dev is None or not np.array_equal(self._host, host_arr):
            self._host = host_arr.copy()
            self._dev = self._put(host_arr)
        return self._dev


class _Inflight:
    """A dispatched-but-unprocessed decode chunk (pipelined decode).

    Holds device handles for the chunk's sampled tokens and the final carry
    (last token + position per lane), plus the lane→sequence snapshot at
    dispatch time. The engine dispatches chunk N+1 off these handles before
    fetching chunk N's results, so the result fetch and the host-side token
    processing overlap the next chunk's compute instead of leaving the chip
    idle between dispatches. The idle share this removes is not measured on
    the current machine.
    """

    __slots__ = ("out", "lps", "top_ids", "top_lps", "tokens", "positions",
                 "lanes", "sealing", "sums", "drafts")

    def __init__(self, out, lps, top_ids, top_lps, tokens, positions, lanes,
                 sealing=None, sums=None, drafts=None):
        self.sums = sums  # device, the counters of a module's own program
        # device [S], a drafting module's guess at the token after the last
        self.drafts = drafts
        self.out = out  # [S, k_steps] device
        self.lps = lps  # [S, k_steps] device, chosen-token logprobs
        self.top_ids = top_ids  # [S, k_steps, P] device
        self.top_lps = top_lps  # [S, k_steps, P] device
        self.tokens = tokens  # [S] device, final carry
        self.positions = positions  # [S] device, final carry
        # the lanes that were LIVE in this dispatch, by slot: None for an
        # empty slot and for a lane that was prefilling then (position -1
        # in-jit: its row and its carry are garbage). Decided at dispatch,
        # because a later chunk can finish that lane's prefill before this
        # dispatch is processed, after which it reads as a decode lane.
        self.lanes = lanes  # List[Optional[_Seq]]
        self.sealing = sealing  # Optional[_SealPages]: the blocks it fills


class _SealPages:
    """The pages of some blocks, taken off the pool by a program enqueued
    behind the one that fills them to their end, their host copy started
    then: the bytes the seal-time checksum is of. Those blocks seal when the
    dispatch's result is processed; taken as it was dispatched
    (`_take_sealing`) they are on the host by then, where a read at the seal
    queues behind whatever was dispatched since (the decode program of the
    same host step, 48 ms). Either way the checksum worker waits for them
    (`host`), not the step loop."""

    __slots__ = ("pages", "where", "_host")

    def __init__(self, pages, where):
        self.pages = pages  # kv_pages.Pages on the device, copy in flight
        self.where = where  # Dict[int, int]: block id -> its column in pages
        self._host = None

    def host(self):
        """The set on the host, assembled once. The checksum worker's alone."""
        if self._host is None:
            # dynlint: allow-host-sync(seal-time checksum, off the engine
            # thread: the copy was started when the pages were taken)
            self._host = kv_pages.to_host(self.pages)
            self.pages = None
        return self._host


class _ChunkInflight:
    """A chunk dispatch whose result has not been fetched: the device handles
    of its sampled tokens, and per live row the lane, its sequence and the
    prompt tokens it fed (a lane that took several rows appears in each, in
    order). It lives inside one host step (`_prefill_step`)."""

    __slots__ = ("fetch", "rows", "sealing", "t_step", "prof")

    def __init__(self, fetch, rows, sealing, t_step, prof):
        self.fetch = fetch  # (sampled [R],) or (sampled, lp, top_ids, top_lps)
        self.rows = rows  # List[Tuple[int, _Seq, List[int]]]: lane, seq, fed; by row
        self.sealing = sealing  # Optional[_SealPages]: the blocks it fills
        self.t_step = t_step  # for the straggler detector alone
        self.prof = prof  # the timeline samples this dispatch


# How many dispatches' worth of sealed pages may wait for the checksum worker
# before the step loop waits for it: a host step is a chunk and a decode
# dispatch, so two steps. Enough to ride out a worker that is late by a step,
# and no more host memory than that (32 blocks, 29 MB, a dispatch under tp=4).
_SEAL_BACKLOG_DISPATCHES = 4


def chunk_row_ladder(max_slots: int) -> List[int]:
    """The row counts a chunk dispatch may have, ascending: ``max_slots`` (an
    admission wave fills every lane at once) and at most two rungs under it,
    an eighth and a quarter of the slots. Under load a request prefills for a
    few of the host steps it lives, so 1-4 of 32 lanes prefill in most chunk
    dispatches and up to a quarter in nearly all the rest (PERF.md 6, PR 32);
    each rung is one more program to compile and warm. A row is a lane that
    prefills or, where a lane may take several (`chunk_rows_of`), one piece
    of a lane's prompt: the second rung is then what a prompt of eight chunks
    prefills in at once."""
    return sorted({r for r in (max_slots // 8, max_slots // 4) if r >= 1} | {max_slots})


def chunk_rows_of(need: List[int], waited: List[float], rungs: List[int],
                  top_takes: bool = False, most: Optional[int] = None) -> List[int]:
    """The rows each prefilling lane takes of a chunk dispatch, where a lane
    may fill several with successive pieces of its prompt (the model module's
    ``LANE_TAKES_ROWS``). ``need[i]`` is the rows lane i's remaining prompt
    asks for, ``waited[i]`` when its request arrived, ``rungs`` the engine's
    ladder; the dispatch is the smallest rung that holds the rows taken.
    ``top_takes``: the module's full-width program reads the rows' lanes too
    (its ``FULL_WIDTH_TAKES_ROWS``); ``most``: the rows of one dispatch its
    module lets one lane fill (its ``lane_rows_most``; None = as many as fit).

    That rung is the smallest that holds ``max(n, min(W, r2))``: the n lanes,
    and of the W rows they ask for as many as the largest rung under
    ``max_slots`` holds (r2), so the full width is never taken for the sake
    of pieces. Every lane gets its first row (none is starved); the rows left
    go to further pieces, the lane that has waited longest first, and what
    does not fit goes on in the next step. At the full width, which the
    number of lanes alone forces, the rows no lane's first piece fills are
    dealt the same way where ``top_takes`` (the program computes them whether
    they hold tokens or not); otherwise a lane has one row there: the program
    is the one without lanes (that of an engine of one rung, to the
    character), or one that reads them only under the full width."""
    if most is not None:
        need = [min(k, most) for k in need]
    n, full = len(need), rungs[-1]
    under = max((r for r in rungs if r < full), default=0)
    rows = next(r for r in rungs if r >= max(n, min(sum(need), under)))
    takes = [1] * n
    if rows < full or top_takes:
        spare = rows - n
        for i in sorted(range(n), key=lambda i: waited[i]):
            takes[i] += min(need[i] - 1, spare)
            spare -= takes[i] - 1
    return takes


class JaxServingEngine(AsyncEngine):
    """Continuous-batching paged-KV engine over a jitted Llama step."""

    def __init__(
        self,
        model_config: LlamaConfig,
        params: Any,
        engine_config: EngineConfig = EngineConfig(),
        mesh=None,
        event_sink: Optional[KvEventSink] = None,
        cache_dtype: Any = None,
    ):
        self.model_config = model_config
        self.config = engine_config
        # the model module, picked in one place (models.module_for). Two
        # facts about it, each stated by what the module has:
        # - ``COUNTERS``: it brings its OWN step programs (``forward_chunk``
        #   / ``decode`` in the form called here, with the slots' state in
        #   and out and the sums its layers count), where `models/llama.py`'s
        #   are imported by name. That picks the call form, and nothing else.
        # - ``make_slot_state``: it keeps state per SLOT beside the pages (a
        #   recurrent layer's), which the programs carry: whatever hands
        #   pages over without it is refused by name (`_refuse_for_state`:
        #   no prefix hit, no draft, no host tier, no transfer). A module
        #   with its own programs and NO such state (`models/openpangu.py`:
        #   latent pages and nothing else) is handed ``None`` for the state
        #   and is refused none of them.
        self.model = module_for(model_config)
        self._own_programs = hasattr(self.model, "COUNTERS")
        self._slot_model = hasattr(self.model, "make_slot_state")
        # - ``SERVES_ON_MESH``: its own programs, its pool and its state run
        #   sharded: it is handed the mesh (``mesh=``) wherever it makes or
        #   steps them, and lays itself over it. Any other module with its
        #   own programs is refused a mesh.
        self._on_mesh = {"mesh": mesh} if mesh is not None and self._own_programs else {}
        if self._own_programs and (
            (mesh is not None and not getattr(self.model, "SERVES_ON_MESH", False))
            or engine_config.quantize
            or (engine_config.kv_dtype or env_kv_dtype()) == "int8"
        ):
            raise ValueError(
                f"{type(model_config).__name__} runs on one device, with "
                "bf16 weights and native pages"
            )
        if engine_config.quantize == "int8-all":
            # int8 for BOTH phases, bf16 tree dropped: the fit mode for
            # models whose bf16 weights alone exceed the chip (llama3-8b =
            # 16.06 GB on a 16 GB v5e). Prefill pays the dequant cost;
            # callers with host-quantized trees pass them directly so the
            # full bf16 tree never has to exist in HBM.
            from dynamo_tpu.models.llama import quantize_params_int8

            def _is_quantized(tree):
                lay = tree.get("layers", {}) if isinstance(tree, dict) else {}
                return isinstance(lay.get("wq"), dict)

            qp = (
                params if _is_quantized(params)
                else quantize_params_int8(params, model_config)
            )
            self.params = params = qp
            self.params_decode = qp
        elif engine_config.quantize == "int8":
            from dynamo_tpu.models.llama import quantize_params_int8

            # hybrid: DECODE reads the int8 copy (weights are the decode
            # bandwidth roofline — the stream halves), PREFILL keeps bf16
            # (it is FLOPs-bound and per-tile dequant converts starve the
            # MXU — measured 13x slower chunks). Costs 1.5x param residency.
            if mesh is not None:
                # sharded serving: quantize under jit with out_shardings so
                # each {q, s} leaf lands sharded like its parent weight
                # (scales keep every non-contracted axis) — the 70B north
                # star serves int8 on the dp×tp mesh. Works on a process-
                # spanning mesh too: every host runs this jit in lockstep.
                from dynamo_tpu.models.llama import quantized_param_shardings

                quant = jax.jit(
                    lambda p: quantize_params_int8(p, model_config),
                    out_shardings=quantized_param_shardings(model_config, mesh),
                )
                self.params_decode = quant(params)
            else:
                self.params_decode = quantize_params_int8(params, model_config)
        elif engine_config.quantize:
            raise ValueError(f"unknown quantize mode {engine_config.quantize!r}")
        else:
            self.params_decode = params
        self.params = params
        self.mesh = mesh
        # self-draft speculative decoding knobs (engine_jax/drafter.py):
        # config wins when set, else the clamped env parsers. spec_k = 0 is
        # the off default — the decode path then never touches a drafter.
        sk = (
            engine_config.spec_k if engine_config.spec_k is not None
            else env_spec_k()
        )
        # a drafted token that is rejected would have to be taken out of
        # the slot's recurrent state again: such a model never drafts
        self._spec_k = 0 if self._slot_model else max(0, min(int(sk), MAX_SPEC_K))
        # a module with a prediction module of its own (``draft_chunk``)
        # drafts on the device, inside the dispatch that made the token
        # before (engine_jax/drafter.py:DeviceDrafter); every other model's
        # drafts come from the host's n-gram index
        self._device_drafts = self._spec_k > 0 and hasattr(self.model, "draft_chunk")
        self._spec_ngram = (
            engine_config.spec_ngram if engine_config.spec_ngram is not None
            else env_spec_ngram()
        )
        # KV page storage dtype: int8 pages + per-token scale tables halve
        # the KV half of the decode stream. Single-chip only for now — the
        # sharded cache path and the Pallas kernel have no dequant tier yet
        # (ROADMAP item 2 pairs them).
        if engine_config.kv_dtype not in (None, "bf16", "int8"):
            # the env parser deliberately degrades typos to the native
            # layout (a typo must never silently quantize a fleet), but an
            # explicit config value is a programming error: "INT8" silently
            # measuring bf16 would invalidate a whole benchmark run
            raise ValueError(
                f"kv_dtype={engine_config.kv_dtype!r} not in "
                "{None, 'bf16', 'int8'}"
            )
        kd = engine_config.kv_dtype or env_kv_dtype()
        self._kv_quantized = kd == "int8"
        if self._kv_quantized and mesh is not None:
            raise ValueError(
                "kv_dtype='int8' requires an unsharded cache (mesh=None); "
                "sharded engines keep the native KV dtype"
            )
        # multihost lockstep: every host array entering a global-mesh jit is
        # built as a replicated global array (jnp.asarray cannot span
        # processes); single-host configs take the plain path
        self._multihost = mesh is not None and jax.process_count() > 1
        self._dispatch_hook = None  # multihost leader: broadcast dispatches
        self.num_blocks = engine_config.resolve_num_blocks()
        if engine_config.host_cache_blocks > 0:
            self._refuse_for_state("the host tier")
        self.host_pool = (
            HostKvPool(engine_config.host_cache_blocks)
            if engine_config.host_cache_blocks > 0
            else None
        )
        # integrity plane (runtime/integrity.py, docs/resilience.md §Silent
        # corruption): block content checksums at seal + the output
        # watchdog. None with DYN_TPU_KV_INTEGRITY=0 — THE zero-overhead
        # gate: no checksum callback is installed, no watchdog variant is
        # built, every jitted program is exactly the pre-integrity one.
        self._integrity = integrity_mod.maybe_from_env()
        # the watchdog rides the jitted step functions as one extra scalar
        # input + a sentinel substitution; sharded/multihost engines keep
        # the pre-integrity dispatch protocol (followers replay the
        # leader's opcode stream — an extra input would skew it), so the
        # watchdog is single-chip for now, like int8 KV.
        self._watchdog = self._integrity is not None and mesh is None
        # seal-time checksums pull the sealed pages to this host: on a
        # multi-process mesh the pool spans devices no one process can
        # address (device_get raises), so those engines seal unchecked and
        # say so through the kv_seal_checksums gauge
        self._seal_checksums = self._integrity is not None and not self._multihost
        # who computes them: a thread of its own (engine_jax/seal_crc.py),
        # none where nothing is checksummed. Cumulative: blocks handed to it,
        # and how often the engine thread waited for it, as a reader of a crc
        # still pending and at the bound on what may be pending.
        self._crc_worker = (
            SealCrcWorker("jax-engine-seal-crc") if self._seal_checksums else None
        )
        self.seal_crc_blocks = 0
        self.seal_crc_reader_waits = 0
        self.seal_crc_reader_wait_us = 0.0
        self.seal_crc_backlog_waits = 0
        # label the fault gates match on ("corrupt"/"poison" drills target
        # ONE worker in a fleet); attach_kv_publishing stamps the worker id
        self._fault_addr = "engine"
        self.allocator = BlockAllocator(
            self.num_blocks, engine_config.kv_block_size, event_sink=event_sink,
            host_pool=self.host_pool,
            offload=self._offload_blocks if self.host_pool is not None else None,
            checksum=self._seal_crcs if self._seal_checksums else None,
            await_crc=self._seal_await if self._seal_checksums else None,
        )

        # attention impl is auto-selected (platform + head-dim rule,
        # ops/attention.py); on a sharded cache the kernel runs per-tp-shard
        # under shard_map — `mesh` is passed into forward so the kernel tier
        # stays live in sharded (70B-path) configs instead of falling back
        # to jnp. The pool is created ON-device via out_shardings (zeros
        # never round-trip the host, and on a multi-process mesh each host
        # materializes only its shards — device_put cannot span processes).
        cdtype = cache_dtype or model_config.dtype
        # compute dtype of attention inputs: int8 pages dequantize into this
        # (and the decode window buffers are allocated in it — never in the
        # pool's storage dtype)
        self._compute_dtype = cdtype
        if mesh is not None and not self._own_programs:
            from dynamo_tpu.parallel.mesh import kv_cache_sharding

            sh = kv_cache_sharding(mesh)
            cshape = (
                model_config.num_layers, self.num_blocks,
                engine_config.kv_block_size, model_config.num_kv_heads,
                model_config.head_dim,
            )
            make = jax.jit(
                lambda: {"k": jnp.zeros(cshape, cdtype), "v": jnp.zeros(cshape, cdtype)},
                out_shardings={"k": sh, "v": sh},
            )
            self.cache = make()
        else:
            # the caller's dtype or the module's own (llama: the model's);
            # a module that serves on a mesh makes its pool in its shardings
            self.cache = self.model.make_kv_cache(
                model_config, self.num_blocks, engine_config.kv_block_size,
                dtype=cache_dtype, quantized=self._kv_quantized,
                # the prediction module's own pages, only where it runs
                **({"drafting": True} if self._device_drafts else {}),
                **self._on_mesh,
            )
        # the slots' state, one value the model module owns: the step
        # programs take it and hand it back, nothing here looks inside
        self.slot_state = (
            self.model.make_slot_state(
                model_config, engine_config.max_slots, **self._on_mesh)
            if self._slot_model else None
        )
        # what of that state ONE chip holds (a module that serves on a mesh
        # shards it; /debug/engine shows it beside the module's counters)
        self._slot_state_bytes_a_chip = sum(
            math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
            for a in jax.tree.leaves(self.slot_state)
        )
        # sums a module's own programs return (its COUNTERS), added up by
        # the host as their dispatches are fetched
        self.model_counters: Dict[str, int] = {
            name: 0 for name in getattr(self.model, "COUNTERS", ())
        }
        self.prefix_hits_declined = 0

        S = engine_config.max_slots
        MB = engine_config.max_blocks_per_seq
        self._slots: List[Optional[_Seq]] = [None] * S
        self._tables = np.zeros((S, MB), np.int32)
        self._last_tokens = np.zeros((S,), np.int32)
        self._positions = np.full((S,), -1, np.int32)
        self._temp = np.zeros((S,), np.float32)
        self._topk = np.zeros((S,), np.int32)
        self._topp = np.ones((S,), np.float32)
        self._seeds = np.zeros((S,), np.int32)
        self._freqp = np.zeros((S,), np.float32)
        self._presp = np.zeros((S,), np.float32)

        # frequency/presence penalties: [S, V] output-token count buffer,
        # device-resident, maintained in-jit (sampling.apply_penalties /
        # update_counts). Allocated lazily on the first penalized request;
        # the dummy stands in when no lane is penalized so the two step-fn
        # variants share one signature. `_counts_lanes` records which _Seq
        # each row's contents belong to (identity), so admissions into a
        # slot reset + rebuild only the rows that changed.
        self._counts: Optional[jax.Array] = None
        if self._multihost:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(mesh, PartitionSpec())
            self._dummy_counts = jax.jit(
                lambda: jnp.zeros((S, 1), jnp.int32), out_shardings=rep
            )()
        else:
            self._dummy_counts = jnp.zeros((S, 1), jnp.int32)
        # upload caches for the per-dispatch host arrays (see _DevMirror)
        self._m_tables = _DevMirror(self._put)
        self._m_ipack = _DevMirror(self._put)
        self._m_fpack = _DevMirror(self._put)
        self._counts_lanes: List[Optional[_Seq]] = [None] * S
        self._counts_sync_fns: Dict[Tuple[int, int], Any] = {}
        self._counts_fix_fns: Dict[int, Any] = {}

        self._step_counter = 0

        self._pending: Deque[_Seq] = deque()
        self._cond = threading.Condition()
        self._shutdown = False
        self._thread: Optional[threading.Thread] = None

        # pipelined decode: at most one dispatched-but-unprocessed chunk, plus
        # allocations whose blocks may still receive speculative writes from
        # the in-flight chunk (freed only once it has been fetched)
        self._inflight: Optional[_Inflight] = None
        self._zombie_allocs: List[SequenceAllocation] = []

        # disaggregated prefill: policy decides + submits; sequences wait in
        # _awaiting until the prefill worker's KV lands (complete_remote_prefill)
        self._remote_policy: Optional[Any] = None
        self._awaiting: Dict[str, _Seq] = {}
        self._posted: Deque[Any] = deque()  # host fns to run on the engine thread
        # serializes posted-callback execution once close() removes the
        # engine thread as the single executor (post-close inline runs).
        # Reentrant: a posted callback may itself post (e.g. a failed
        # complete_remote_prefill falls back via fail_remote_prefill), and
        # post-close that nested post runs inline on the same thread.
        self._posted_exec_lock = threading.RLock()

        # prefill-worker mode: requests whose pages are parked on finish so
        # the worker can extract them (hold_pages / take_held_pages)
        self._hold_ids: set = set()
        self._held_allocs: Dict[str, SequenceAllocation] = {}

        # host-tier spills in flight: (pairs, device pages) whose async host
        # copies haven't been harvested into the host pool yet
        self._pending_spills: Deque[
            Tuple[List[Tuple[int, int, Any]], kv_pages.Pages]
        ] = deque()

        # live in-flight migration (disagg/migration.py, docs/resilience.md
        # §Live migration). Source side: sequences frozen out of their slots
        # while the drain coordinator ships their pages. Target side: staged
        # imports — a pre-built allocation whose cached_tokens covers every
        # already-computed position, keyed by migration id, waiting for the
        # re-homed client's attach (TTL-swept if it never comes). Both dicts
        # stay empty unless a drain migration is actually in flight — the
        # step loop pays nothing for the feature existing.
        self._migrating_out: Dict[str, _Seq] = {}
        self._staged_migrations: Dict[str, Tuple[SequenceAllocation, tuple, float]] = {}

        # stats
        self.total_requests = 0
        self.total_generated_tokens = 0
        self.total_prompt_tokens = 0
        self.preemptions = 0
        # mid-stream resume (docs/resilience.md): requests admitted with a
        # resume marker — their prompt is another worker's dead stream
        self.resumed_requests = 0
        # live migration counters: streams this engine shipped out on drain,
        # staged imports adopted by a re-homed client, and — the chaos-gate
        # observable — prompt positions a RESUMED/MIGRATED admission had to
        # recompute (a migrated stream adds 0; a plain resume adds the whole
        # uncached history)
        self.migrated_out_requests = 0
        self.migrated_in_requests = 0
        self.migrations_failed = 0
        self.resume_recompute_tokens = 0
        # output watchdog (docs/resilience.md §Silent corruption): lanes
        # whose dispatch produced non-finite/exploding logits — each ended
        # typed and in-band (resume directive) before any token reached a
        # client, and counted as an integrity trip against this worker
        self.watchdog_trips = 0
        # speculative decoding (cumulative): drafts handed to verify
        # dispatches and how many matched their sampled targets
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0

        # health plane: the step loop beats this once per iteration; a busy
        # engine whose beats stop is a wedged engine thread (device hang,
        # deadlocked posted callback) — runtime/health.py HealthMonitor
        # turns that into an `unhealthy` self-drain
        self.heartbeat = EngineHeartbeat()

        # live perf accounting (telemetry plane): None when sampling is off,
        # so the step loop's only cost is this attribute's None-check
        self._perf: Optional[_EnginePerf] = (
            _EnginePerf() if telemetry.enabled() else None
        )

        # performance attribution plane (runtime/profiling.py,
        # docs/observability.md §Profiling): per-dispatch device/host/alloc
        # timing into the process-global StepTimeline ring. None with
        # DYN_TPU_PROFILE off — the step loop then pays one None-check per
        # dispatch and no timeline is ever constructed (the zero-overhead
        # guard in tests/test_profiling.py monkeypatches the constructor).
        self._profile = profiling_mod.maybe_from_env()
        self._timeline = (
            profiling_mod.timeline() if self._profile is not None else None
        )
        # the engine thread's ONE clock (runtime/profiling.py:PhaseClock):
        # every phase of a host step is a span on the profiler's clock while
        # a session runs, and a self-time counter in /debug/engine always
        self._clock = profiling_mod.PhaseClock(
            profiling_mod.ENGINE_PHASES, "engine.", jax.profiler.TraceAnnotation,
            jax.profiler.StepTraceAnnotation, stall_s=0.5,
        )
        # enqueue -> slot, summed over the requests admitted (cumulative)
        self.queue_wait_us_sum = 0.0
        self.queue_wait_count = 0

        # fail-slow defense (runtime/straggler.py, docs/resilience.md
        # §Fail-slow): per-dispatch wall-us-per-token EWMA feeding the
        # aggregator's differential straggler verdicts. None with
        # DYN_TPU_STRAGGLER off — the step loop then pays one None-check
        # per dispatch and no detector is ever constructed (the
        # zero-overhead guard in tests/test_straggler.py monkeypatches
        # the constructor). Independent of the profiling plane: the
        # straggler feed needs EVERY dispatch's coarse wall split, not a
        # sampled block-until-ready capture.
        self._straggler = straggler_mod.maybe_detector()

        # multi-tenant QoS (runtime/qos.py, docs/qos.md): policy + weighted
        # fair-queue bookkeeping, built ONLY when DYN_TPU_TENANT_* knobs are
        # set — the single-tenant step loop pays one None-check (asserted by
        # tests/test_qos.py's zero-overhead guard, the _EnginePerf pattern).
        self._qos = qos_mod.maybe_from_env()
        self._fair: Optional[qos_mod.FairQueue] = (
            qos_mod.FairQueue(self._qos.max_tenants)
            if self._qos is not None else None
        )
        # prefill duty-cycle budget (chunked-prefill interleaving): average
        # prefill tokens per dispatch while decode lanes are live; config
        # wins when set, else the clamped env knob; 0 = unlimited. The
        # debt counter is the duty-cycle state (see _dispatch_step).
        pb = engine_config.prefill_budget
        self._prefill_budget = (
            qos_mod.env_prefill_budget() if pb is None else max(int(pb), 0)
        )
        self._prefill_debt = 0.0
        # per-tenant KV-block budget: binds only while other tenants are
        # active (work-conserving — a tenant alone may use the whole pool)
        self._tenant_kv_budget = (
            max(1, int(self._qos.kv_frac * self.num_blocks))
            if self._qos is not None and self._qos.kv_frac > 0
            else 0
        )
        # per-tenant decode-slot budget: the same work-conserving contract
        # over concurrency — a tenant at its slot share defers while other
        # tenants are active, and alone it may fill the whole batch
        self._tenant_slot_budget = (
            max(1, int(self._qos.slot_frac * engine_config.max_slots))
            if self._qos is not None and self._qos.slot_frac > 0
            else 0
        )
        # high-water mark of prefill tokens computed in a single host step
        # beside a live decode lane — the chunked-prefill interleaving
        # bound the ITL-isolation test asserts against the step budget
        self.prefill_interleave_max = 0
        # tiles of pool history the history-bearing chunk dispatches read (the
        # program's own trip count, from the same positions), and what the
        # block tables' full width would have been
        self.chunk_history_tiles_read = 0
        self.chunk_history_tiles_full = 0
        # (lane, tile) slots of history the dense-tier decode dispatches
        # gathered and attended (the program's own width, from the same
        # positions), and the slots of every table's full width
        self.decode_history_tiles_read = 0
        self.decode_history_tiles_full = 0
        # how full the chunk dispatches are (cumulative): positions computed
        # (rows x prefill_chunk), prompt tokens among them, rows dispatched,
        # rows that held a prefilling lane, and dispatches by row count
        self.chunk_positions_dispatched = 0
        self.chunk_tokens_fed = 0
        self.chunk_rows_dispatched = 0
        self.chunk_rows_live = 0
        self.chunk_dispatches_by_rows: Dict[int, int] = {}
        # distinct lanes the chunk dispatches fed, summed (chunk_rows_live
        # over it: the rows a lane took of a dispatch); the same for the lanes
        # that prefill alone, and the prompts whose last token went through
        # (prompt_dispatches over it: the chunk dispatches a prompt took)
        self.chunk_lanes_fed = 0
        self.prompt_dispatches = 0
        self.prompts_prefilled = 0

        # (with_logprobs, with_penalties, with_sampling) variants, compiled
        # lazily per need; the chunk's key also holds (with_history, rows)
        self._decode_fns: Dict[Tuple[bool, bool, bool], Any] = {}
        self._chunk_fns: Dict[Tuple[bool, bool, bool, bool, int], Any] = {}
        # speculative-verify variants (same key space); never built with
        # spec_k == 0 — asserted by the zero-overhead guard test
        self._verify_fns: Dict[Tuple[bool, bool, bool], Any] = {}

        # decode history tier, fixed at build time (the attention policy env
        # vars are read here rather than per-trace). Both tiers are window-
        # buffered; see ops/attention.py decode_uses_pallas for the policy.
        from dynamo_tpu.ops.attention import decode_uses_pallas

        mc, ec = model_config, engine_config
        dtype_size = jnp.dtype(cache_dtype or mc.dtype).itemsize
        if self._own_programs:
            # its module gathers the paged members to a dense buffer once a
            # dispatch: the jnp tier, and no kernel
            self._decode_dense = True
        else:
            hist_bytes = (
                2 * mc.num_layers * ec.max_slots * ec.max_blocks_per_seq
                * ec.kv_block_size * mc.num_kv_heads * mc.head_dim * dtype_size
            )
            self._decode_dense = not decode_uses_pallas(
                mc.head_dim, mesh, mc.num_heads, mc.num_kv_heads,
                dense_history_bytes=hist_bytes,
                dense_history_budget=ec.dense_history_max_bytes,
            )
        if self._kv_quantized:
            # the Pallas kernel has no fused dequant: int8 pools pin the
            # dense decode-history tier (gather_history dequantizes). The
            # dense buffer is transient compute-dtype working set the
            # einsums needed anyway; the HBM *read* is the halved int8 one.
            self._decode_dense = True

        # pipeline parallelism: when the mesh has a pp axis > 1, step fns
        # route through parallel/pipeline.py's GPipe schedule (layer stages
        # + microbatched slots over ICI ppermute) instead of the
        # single-program layer scan
        from dynamo_tpu.parallel.mesh import AXIS_PP, AXIS_SP

        self._pp = (
            mesh.shape[AXIS_PP]
            if mesh is not None and AXIS_PP in mesh.axis_names
            else 1
        )
        if self._pp > 1:
            if mc.num_layers % self._pp:
                raise ValueError(
                    f"num_layers {mc.num_layers} not divisible by pp {self._pp}"
                )
            if ec.max_slots % self._pp:
                raise ValueError(
                    f"max_slots {ec.max_slots} not divisible by pp {self._pp}"
                    " (slots are the GPipe microbatch axis)"
                )

        # sequence parallelism: prefill chunks ring-attend over sp
        # (models/llama.py forward_chunk_sp); decode is a single position
        # per lane, which sp neither helps nor hinders
        self._sp = (
            mesh.shape[AXIS_SP]
            if mesh is not None and AXIS_SP in mesh.axis_names
            else 1
        )
        if self._sp > 1:
            if ec.prefill_chunk % self._sp:
                raise ValueError(
                    f"prefill_chunk {ec.prefill_chunk} not divisible by sp "
                    f"{self._sp} (the chunk's sequence axis shards over sp)"
                )
            if self._pp > 1:
                raise ValueError("pp and sp cannot be combined yet")

        # the pages of the blocks that the dispatch being processed fills
        # (_SealPages), where _seal_crcs looks first
        self._sealing: Optional[_SealPages] = None

        # Row counts of the chunk program (`chunk_row_ladder`), and who keeps
        # the host step from before the rows were packed: the engines whose
        # shapes are fixed by something of their own. A process-spanning
        # mesh's leader broadcasts one chunk shape and one decode shape to its
        # followers, a pipeline's stages microbatch the row axis, and the sp
        # forward has run at one width only. Those have the one rung every
        # engine has and their decode lanes RIDE the chunk dispatch, one
        # token each at the full width; the decode program runs only in steps
        # in which no lane prefills, and sealed blocks are read when they
        # seal, not ahead (`_sealing_sizes` empty). No chip has run those
        # three, so their ladder waits for a run between real chips (ROADMAP
        # D11). Every other engine, a one-process tp or ep mesh among them,
        # takes the host step that one device takes (PERF.md 6, PR 57).
        S = engine_config.max_slots
        self._rides = self._multihost or self._pp > 1 or self._sp > 1
        self._chunk_rungs: List[int] = [S] if self._rides else chunk_row_ladder(S)
        # a lane may fill several rows of a chunk dispatch under the full
        # width with successive pieces of its prompt (`chunk_rows_of`) where
        # the model's module says its chunk program lets a row attend the
        # earlier rows of its lane: whatever it keeps, the module is the one
        # that speaks (`models.module_for`). One WITHOUT state per slot says
        # so when the rows find each other's fresh keys, in the program's
        # hands (`models/llama.py`) or through the pool (`openpangu`,
        # `xing4`); one WITH state when it also hands the state from row to
        # row (`lfm2` a tail; `jamba`, `kimi_linear` and `qwen3_next` inside
        # their recurrence's kernel). Every module says so today; the
        # engines of one rung keep one row a lane (`_rides`).
        self._lane_rows = (
            len(self._chunk_rungs) > 1
            and getattr(self.model, "LANE_TAKES_ROWS", False)
        )
        # ... and AT the full width too, the rows that the lanes' first
        # pieces leave empty, where the module says its chunk program is the
        # same program there (it reads the rows' lanes at every width); and
        # of one dispatch no more rows a lane than the module says it can
        # keep apart (a ring a slot holds so many positions; None: no bound)
        self._top_takes_rows = self._lane_rows and getattr(
            self.model, "FULL_WIDTH_TAKES_ROWS", False
        )
        most = getattr(self.model, "lane_rows_most", None)
        self._lane_rows_most: Optional[int] = (
            most(model_config, engine_config.prefill_chunk) if most else None
        )
        # the block counts _take_sealing reads ahead, ascending. The largest
        # is what a decode dispatch or a chunk dispatch of a rung under
        # max_slots can fill (an admission wave at the full width seals
        # through the plain read: nothing is dispatched behind it that it
        # would wait for); under it powers of four, so that a read is at most
        # four times what was asked for and few shapes of the take program
        # compile (`warmup` runs each once, and `setup_s` is judged).
        per_row = -(-engine_config.prefill_chunk // engine_config.kv_block_size)
        most = max(S, max(
            [r for r in self._chunk_rungs if r < S], default=0
        ) * per_row)
        self._sealing_sizes: List[int] = [] if self._rides else [
            4 ** e for e in range(16) if 4 ** e < most
        ] + [most]
        # the pages that may wait for the checksum worker, in bytes of the
        # pool: what _SEAL_BACKLOG_DISPATCHES of the largest dispatches read
        # ahead fill. Past it the engine thread waits for the oldest.
        self._block_bytes = sum(a.nbytes for a in self.cache.values()) // self.num_blocks
        self._seal_backlog_bytes = _SEAL_BACKLOG_DISPATCHES * most * self._block_bytes

        # which attention tier the decode programs hold, and whether the
        # kernel is built in Pallas interpret mode (the CPU route of the
        # tests; on a TPU it would be an error). metrics_snapshot() shows it
        # per compiled variant, so a run can tell "served through the
        # kernel" from "ran the reference".
        self._interpret = jax.devices()[0].platform == "cpu"
        if self._pp > 1:
            tier = "pipeline"  # attends through forward()'s own policy
        elif self._decode_dense:
            tier = "dense"
        else:
            from dynamo_tpu.ops.attention import decode_schedule
            from dynamo_tpu.parallel.mesh import AXIS_TP

            tp = (
                mesh.shape[AXIS_TP]
                if mesh is not None and AXIS_TP in mesh.axis_names else 1
            )
            tier = "pallas-" + decode_schedule(
                ec.max_slots, ec.kv_block_size, mc.num_kv_heads // tp,
                mc.head_dim, dtype_size, ec.max_blocks_per_seq,
                sharded=mesh is not None,
            )[0]
        self._decode_tier = {
            "tier": tier,
            "interpret": self._interpret and tier.startswith("pallas-"),
        }

    def _put(self, host_arr) -> jax.Array:
        """Host array → device array usable by the step fns. On a
        process-spanning mesh this builds a REPLICATED global array (every
        process holds the full value — the multihost lockstep contract);
        otherwise a plain transfer. The device array gets a PRIVATE copy
        of the host bytes: the CPU backend aliases a numpy buffer that
        happens to be 64-byte aligned instead of copying it, and the step
        loop rewrites its lane arrays (tables, last tokens, positions) in
        place while earlier dispatches may still be pending — an aliased
        input then changes under a running program (wrong tokens, seen as
        tests that failed only on a loaded machine)."""
        a = np.array(host_arr)
        if not self._multihost:
            return jnp.asarray(a)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.make_array_from_callback(
            a.shape, NamedSharding(self.mesh, PartitionSpec()),
            lambda idx: a[idx],
        )

    # -- jitted step functions ----------------------------------------------

    def _build_decode_fn(self, with_lp: bool = False, with_pen: bool = False,
                         with_sample: bool = True):
        cfg = self.model_config
        k_steps = self.config.decode_steps
        max_pos = self.config.max_model_len - 1
        n_top = self.config.top_logprobs
        dense = self._decode_dense
        # output watchdog (docs/resilience.md §Silent corruption): engine-
        # wide constant, so the variant cache key is unchanged. When on, the
        # fn takes one extra scalar (``wdf``: the poison-drill flag) and
        # substitutes WATCHDOG_TOKEN for any lane whose logits are
        # non-finite or exploding — the host loop detects the tripped lane
        # from the fetched tokens alone, zero extra outputs or transfers.
        wd = self._watchdog
        wd_limit = self._integrity.logit_limit if wd else 0.0

        def _wd_bad(sel, wdf):
            # full_like keeps sel's dtype exactly: the watchdog must not
            # perturb the sampling math of a healthy dispatch in any way
            sel = jnp.where(wdf > 0, jnp.full_like(sel, jnp.nan), sel)
            bad = (~jnp.all(jnp.isfinite(sel), axis=-1)) | (
                jnp.max(jnp.abs(sel), axis=-1) > wd_limit
            )
            return sel, bad

        def sampler(step_ctr, ipack, fpack, wdf):
            """One step's sampling for this dispatch: ``(logits [S, V],
            positions, counts, k) -> (next tokens, next positions, counts,
            what the step hands the host)``, the same in every body below."""
            # ipack [2,S] int32 = (seeds, topk); fpack [4,S] f32 =
            # (temp, topp, freqp, presp). Packed so a dispatch uploads at
            # most two small host arrays (each upload is a separate
            # transfer), cached by _DevMirror.
            # step_ctr: replicated int32 scalar; the step key derives from it
            # IN-JIT so multihost lockstep needs only a number on the wire.
            step_key = jax.random.fold_in(jax.random.PRNGKey(0), step_ctr)
            seeds, topk = ipack[0], ipack[1]
            temp, topp, freqp, presp = fpack[0], fpack[1], fpack[2], fpack[3]

            def sample_step(sel, pos, counts, k):
                if wd:
                    sel, bad = _wd_bad(sel, wdf)
                if with_sample:
                    kk = jax.random.fold_in(step_key, k)
                    keys = jax.vmap(lambda s: jax.random.fold_in(kk, s))(seeds)
                else:
                    keys = None  # unused by the greedy-only sampler
                sampled_from = (
                    apply_penalties(sel, counts, freqp, presp)
                    if with_pen else sel
                )
                nxt = sample_tokens(sampled_from, keys, temp, topk, topp,
                                    greedy_only=not with_sample)
                if wd:
                    nxt = jnp.where(
                        bad & (pos >= 0), jnp.int32(WATCHDOG_TOKEN), nxt
                    )
                if with_pen:
                    counts = update_counts(counts, nxt, pos >= 0)
                new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
                if with_lp:
                    lp, tids, tlps = token_logprobs(sel, nxt, n_top)
                    return nxt, new_pos, counts, (nxt, lp, tids, tlps)
                return nxt, new_pos, counts, nxt

            return sample_step

        def slot_major(out):
            """The scan's stacked outputs [k_steps, S, ...] as the host reads
            them, slot-major."""
            if with_lp:
                out, lps, tids, tlps = out
                return (out.T, lps.T, tids.transpose(1, 0, 2),
                        tlps.transpose(1, 0, 2))
            return (out.T,)

        if self._own_programs:
            drafting = {"draft": True} if self._device_drafts else {}

            def decode(params, cache, state, counts, tokens, positions,
                       tables, step_ctr, ipack, fpack, wdf=None):
                # a module with its own programs (models.module_for): it scans
                # the steps itself, over the slots' state where it keeps one
                # (None otherwise), and hands back the sums its layers count
                # and, where it drafts, its guess at the token after the last
                sample_step = sampler(step_ctr, ipack, fpack, wdf)

                def sample(sel, pos, counts, k):
                    nxt, _, counts, out = sample_step(sel, pos, counts, k)
                    return nxt, counts, out

                toks, pos, counts, out, cache, state, sums, *drafts = self.model.decode(
                    params, cfg, tokens, positions, cache, tables, state,
                    k_steps, max_pos, sample, counts, **drafting, **self._on_mesh,
                )
                return (*slot_major(out), toks, pos, sums, *drafts, cache, state, counts)

            # the trace names a program after its function: jit_decode
            return jax.jit(decode, donate_argnums=(1, 2, 3))

        def decode(params, cache, counts, tokens, positions, tables, step_ctr,
                   ipack, fpack, wdf=None):
            sample_step = sampler(step_ctr, ipack, fpack, wdf)
            # tokens/positions: [S]; tables: [S, MB]. Scans k_steps forward+
            # sample iterations, feeding each sampled token back in — one
            # dispatch yields [S, k_steps] tokens. The final carry (tokens,
            # positions) is returned so the NEXT dispatch can chain off the
            # device-resident state without a host round trip (pipelined
            # decode); a lane whose position would pass max_pos goes to -1 so
            # speculative steps never scatter into a block past its table.
            # The penalty-count buffer rides the same carry, so within-chunk
            # repeats are penalized too.
            #
            # The decode scan is windowed in BOTH attention tiers: the pool is
            # READ-ONLY inside the scan; each step's K/V go to a [L, S, W]
            # window buffer riding the carry (models/llama.py forward_window),
            # flushed to pages after it by ONE in-place scatter per pool array
            # (flush_window) — the dispatch never copies or slices the pool,
            # so its cost follows the lanes, not the pool's size.
            # Only the history read differs (ops/attention.py
            # decode_uses_pallas): the jnp tier pre-gathers pages to a dense
            # buffer once per dispatch (per-step gathers lower to serialized
            # page slices); the kernel tier streams pages HBM→VMEM in the
            # Pallas kernel and merges the window partial flash-decoding
            # style via the kernel's softmax stats.
            if self._pp > 1:
                # pipeline decode: each step is a pipelined single-token
                # forward; the cache rides the scan carry (pages stay on
                # their stage's shard, written by decoder_layer per step).
                # The window structure is not used — GPipe's microbatch
                # schedule already amortizes the per-layer cost, and pages
                # are written stage-locally with no cross-stage scatter.
                from dynamo_tpu.parallel.pipeline import pipeline_forward

                def body_pp(carry, k):
                    toks, pos, cache, counts = carry
                    logits, cache = pipeline_forward(
                        params, cfg, toks[:, None], pos[:, None], cache,
                        tables, self.mesh,
                    )
                    nxt, new_pos, counts, out = sample_step(
                        logits[:, 0], pos, counts, k
                    )
                    return (nxt, new_pos, cache, counts), out

                (toks, pos, cache, counts), out = jax.lax.scan(
                    body_pp, (tokens, positions, cache, counts),
                    jnp.arange(k_steps),
                )
                return (*slot_major(out), toks, pos, cache, counts)

            base = positions
            wshape = (
                cfg.num_layers, self.config.max_slots, k_steps,
                cfg.num_kv_heads, cfg.head_dim,
            )
            # window buffers hold COMPUTE-dtype values even over an int8
            # pool (they are attended directly; flush_window quantizes them
            # on the way into the pages)
            wk0 = jnp.zeros(wshape, self._compute_dtype)
            wv0 = jnp.zeros(wshape, self._compute_dtype)
            def steps(history):
                """``k_steps`` forward + sample iterations over ``history``:
                the scan's final carry and its stacked outputs."""
                def body(carry, k):
                    toks, pos, counts, wk, wv = carry
                    sel, wk, wv = forward_window(
                        params, cfg, toks, pos, history, base, wk, wv, k,
                    )
                    nxt, new_pos, counts, out = sample_step(sel, pos, counts, k)
                    return (nxt, new_pos, counts, wk, wv), out

                return jax.lax.scan(
                    body, (tokens, positions, counts, wk0, wv0),
                    jnp.arange(k_steps),
                )

            # the dense tier on one device gathers and attends the history
            # that is live, at a width the program takes from `base`
            # (models/llama.py with_live_history). A mesh engine keeps every
            # table's full width: with the pool sharded a KV head a shard the
            # live form's (lane, head) slots cross the shards, and a step read
            # 10.06 ms where this form reads 7.22 under tp=4, with a second
            # width to warm up (PERF.md 6, PR 57)
            if dense and self.mesh is not None:
                hist_k, hist_v = gather_history(
                    cache, tables, out_dtype=self._compute_dtype
                )
                done = steps(("dense", hist_k, hist_v))
            elif dense:
                done = with_live_history(
                    cache, tables, base, steps, out_dtype=self._compute_dtype
                )
            else:
                done = steps(
                    ("paged", cache, tables, self.mesh, self._interpret)
                )
            (toks, pos, counts, wk, wv), out = done
            cache = flush_window(cache, tables, base, wk, wv, max_pos)
            return (*slot_major(out), toks, pos, cache, counts)

        if self._multihost:
            # leader must device_get sampled tokens/carries: pin every output
            # except the cache to a replicated sharding (tiny all-gathers)
            rep, cache_sh = self._io_shardings()
            n_extra = 6 if with_lp else 3
            out_sh = (rep,) * n_extra + ({"k": cache_sh, "v": cache_sh}, rep)
            return jax.jit(decode, donate_argnums=(1, 2), out_shardings=out_sh)
        return jax.jit(decode, donate_argnums=(1, 2))

    def _io_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec

        from dynamo_tpu.parallel.mesh import kv_cache_sharding

        return NamedSharding(self.mesh, PartitionSpec()), kv_cache_sharding(self.mesh)

    def _decode(self, want_lp: bool, want_pen: bool = False,
                want_sample: bool = True):
        """The decode variant with/without logprobs/penalties/sampling (each
        compiled lazily: the logprobs math + its device→host transfer, the
        penalty-count scatter, and the top-k/categorical sampling block stay
        off the hot path when no live lane asked for them)."""
        key = (want_lp, want_pen, want_sample)
        fn = self._decode_fns.get(key)
        if fn is None:
            self._clock.compile_key = record_compile("decode", detail=(
                f"lp={want_lp} pen={want_pen} sample={want_sample} "
                f"[S={self.config.max_slots},k={self.config.decode_steps}]"
            ))
            fn = self._decode_fns[key] = self._build_decode_fn(
                want_lp, want_pen, want_sample
            )
        return fn

    def _chunk(self, want_lp: bool, want_pen: bool = False,
               want_sample: bool = True, want_history: bool = True,
               rows: Optional[int] = None):
        """The chunk variant at ``rows`` rows (a rung of ``_chunk_rungs``;
        default ``max_slots``). One jitted function serves every row count:
        the key keeps the programs apart that ``warmup`` compiled ahead."""
        if self._pp > 1 or self._sp > 1 or self._own_programs:
            want_history = True  # these forwards have no history-free variant
        rows = self.config.max_slots if rows is None else rows
        key = (want_lp, want_pen, want_sample, want_history, rows)
        fn = self._chunk_fns.get(key)
        if fn is None:
            self._clock.compile_key = record_compile("chunk", detail=(
                f"lp={want_lp} pen={want_pen} sample={want_sample} "
                f"history={want_history} [R={rows},"
                f"C={self.config.prefill_chunk}]"
            ))
            fn = self._chunk_fns[key] = self._build_chunk_fn(
                want_lp, want_pen, want_sample, want_history,
                # under the full width a lane may fill several rows
                # (`_chunk_build`): the program is told the rows' lanes
                with_lanes=self._lane_rows and rows < self.config.max_slots,
            )
        return fn

    def _build_chunk_fn(self, with_lp: bool = False, with_pen: bool = False,
                        with_sample: bool = True, with_history: bool = True,
                        with_lanes: bool = False):
        cfg = self.model_config
        n_top = self.config.top_logprobs
        wd = self._watchdog
        wd_limit = self._integrity.logit_limit if wd else 0.0

        def sampling_inputs(step_ctr, ipack, fpack):
            """Unpacked at the head of the program, where the parent's text
            has them: the programs of `models/llama.py` stay what they were,
            to the character (and the compile cache's entries with them)."""
            step_key = jax.random.fold_in(jax.random.PRNGKey(0), step_ctr)
            return (step_key, ipack[0], ipack[1],
                    fpack[0], fpack[1], fpack[2], fpack[3])

        def sample_rows(params, h, counts, sample_at, lanes, inputs, wdf):
            """The rows' sampled tokens off the chunk's hidden states: (what
            the host fetches, the penalty counts)."""
            step_key, seeds, topk, temp, topp, freqp, presp = inputs
            hs = h[jnp.arange(h.shape[0]), jnp.clip(sample_at, 0)]  # [R, E]
            sel = self.model.lm_head(params, cfg, hs)  # [R, V]
            if wd:
                # output watchdog: poison-drill substitution + per-lane
                # non-finite/exploding flag → WATCHDOG_TOKEN sentinel
                sel = jnp.where(wdf > 0, jnp.full_like(sel, jnp.nan), sel)
                bad = (~jnp.all(jnp.isfinite(sel), axis=-1)) | (
                    jnp.max(jnp.abs(sel), axis=-1) > wd_limit
                )
            if with_sample:
                keys = jax.vmap(lambda s: jax.random.fold_in(step_key, s))(seeds)
            else:
                keys = None
            sampled_from = (
                apply_penalties(
                    sel, counts.at[lanes].get(mode="fill", fill_value=0),
                    freqp, presp,
                ) if with_pen else sel
            )
            nxt = sample_tokens(sampled_from, keys, temp, topk, topp,
                                greedy_only=not with_sample)
            if wd:
                nxt = jnp.where(
                    bad & (sample_at >= 0), jnp.int32(WATCHDOG_TOKEN), nxt
                )
            if with_pen:
                counts = update_counts(counts, nxt, sample_at >= 0, rows=lanes)
            if with_lp:
                return (nxt, *token_logprobs(sel, nxt, n_top)), counts
            return (nxt,), counts

        if self._device_drafts:
            def chunk(params, cache, state, counts, tokens, positions,
                      tables, sample_at, lanes, following, step_ctr, ipack,
                      fpack, wdf=None):
                # a module that drafts (``draft_chunk``): its prediction
                # module runs over the rows' positions behind the sampling,
                # each with the token that follows it (``following`` [R, C]:
                # the prompt's own, from the host; at ``sample_at`` the token
                # just sampled), writes its own pages, and the host gets its
                # first choice for the token AFTER the sampled one
                inputs = sampling_inputs(step_ctr, ipack, fpack)
                x, cache, state, sums = self.model.forward_chunk(
                    params, cfg, tokens, positions, cache, tables, state, lanes,
                    raw=True,
                )
                fetch, counts = sample_rows(
                    params, self.model.final_norm(params, cfg, x), counts,
                    sample_at, lanes, inputs, wdf,
                )
                cols = jnp.arange(tokens.shape[1])
                following = jnp.where(
                    cols[None, :] == sample_at[:, None], fetch[0][:, None],
                    following,
                )
                hd, cache, more = self.model.draft_chunk(
                    params, cfg, x, following, positions, cache, tables,
                )
                hs = hd[jnp.arange(hd.shape[0]), jnp.clip(sample_at, 0)]
                drafts = jnp.argmax(
                    self.model.lm_head(params, cfg, hs), axis=-1
                ).astype(jnp.int32)
                return (*fetch, sums + more, drafts, cache, state, counts)

            return jax.jit(chunk, donate_argnums=(1, 2, 3))

        if self._own_programs:
            def chunk(params, cache, state, counts, tokens, positions,
                      tables, sample_at, lanes, step_ctr, ipack, fpack,
                      wdf=None):
                # a module with its own programs (models.module_for): a row
                # starts from its slot's state and leaves it behind (None
                # where the module keeps none); the sums its layers count go
                # to the host
                inputs = sampling_inputs(step_ctr, ipack, fpack)
                h, cache, state, sums = self.model.forward_chunk(
                    params, cfg, tokens, positions, cache, tables, state, lanes,
                    **self._on_mesh,
                )
                fetch, counts = sample_rows(
                    params, h, counts, sample_at, lanes, inputs, wdf
                )
                return (*fetch, sums, cache, state, counts)

            # the trace names a program after its function: jit_chunk
            return jax.jit(chunk, donate_argnums=(1, 2, 3))

        def chunk(params, cache, counts, tokens, positions, tables, sample_at,
                  lanes, step_ctr, ipack, fpack, wdf=None):
            # tokens/positions: [R, C] (−1 positions = padding), a row per
            # prefilling lane or (``with_lanes``) per piece of a lane's
            # prompt, a lane's pieces in consecutive rows in order, packed to
            # the front; sample_at: [R] index of the token whose logits to
            # sample (a lane's last row alone), −1 → output unused; lanes:
            # [R] the slot of each row (max_slots = a padding row), which is
            # its row of the [S, V] penalty counts. R is the inputs' own.
            # The LM head runs on the gathered [R, E] sample positions only —
            # never on the full [R, C, E] chunk (at C=128 that head matmul and
            # its [R, C, vocab] float32 logits dwarf the useful work and sat
            # directly on the TTFT critical path).
            inputs = sampling_inputs(step_ctr, ipack, fpack)
            if self._pp > 1:
                from dynamo_tpu.parallel.pipeline import pipeline_forward

                h, cache = pipeline_forward(
                    params, cfg, tokens, positions, cache, tables, self.mesh,
                    hidden_only=True,
                )
            elif self._sp > 1:
                from dynamo_tpu.models.llama import forward_chunk_sp

                h, cache = forward_chunk_sp(
                    params, cfg, tokens, positions, cache, tables, self.mesh,
                    hidden_only=True,
                )
            else:
                # history/fresh split (models/llama.py forward_chunk): the
                # layer loop only reads the pool; the layers' fresh K/V are
                # written after it by one in-place scatter per pool array
                h, cache = forward_chunk(
                    params, cfg, tokens, positions, cache, tables,
                    hidden_only=True, with_history=with_history,
                    lanes=lanes if with_lanes else None,
                )
            fetch, counts = sample_rows(
                params, h, counts, sample_at, lanes, inputs, wdf
            )
            return (*fetch, cache, counts)

        if self._multihost:
            rep, cache_sh = self._io_shardings()
            n_extra = 4 if with_lp else 1
            out_sh = (rep,) * n_extra + ({"k": cache_sh, "v": cache_sh}, rep)
            return jax.jit(chunk, donate_argnums=(1, 2), out_shardings=out_sh)
        return jax.jit(chunk, donate_argnums=(1, 2))

    def _verify(self, want_lp: bool, want_pen: bool = False,
                want_sample: bool = True):
        """The speculative-verify variant (drafted tokens scored in one
        weight stream; engine_jax/drafter.py). Compiled lazily like the
        decode/chunk variants — and never at all while spec_k == 0."""
        key = (want_lp, want_pen, want_sample)
        fn = self._verify_fns.get(key)
        if fn is None:
            self._clock.compile_key = record_compile("verify", detail=(
                f"lp={want_lp} pen={want_pen} sample={want_sample} "
                f"[S={self.config.max_slots},k1={self._spec_k + 1}]"
            ))
            fn = self._verify_fns[key] = self._build_verify_fn(
                want_lp, want_pen, want_sample
            )
        return fn

    def _build_verify_fn(self, with_lp: bool = False, with_pen: bool = False,
                         with_sample: bool = True):
        """One speculative-verify dispatch: feed ``[last_token, draft_0, ..,
        draft_{k-1}]`` per lane ([S, K1] with -1-position padding), compute
        logits at EVERY fed position in one forward pass, and sample the
        engine's own target token per position (sampling.speculative_targets
        — the point-mass rejection-sampling rule). The host keeps the
        drafted prefix that matches the targets plus the first non-matching
        target as the bonus token, so one weight stream emits up to k+1
        tokens. Unlike the chunk fn, the LM head runs on all K1 positions —
        at K1 ≤ MAX_SPEC_K+1 that head matmul is the price of admission for
        the amortized stream, and it is a fraction of the full chunk head
        this path replaces."""
        cfg = self.model_config
        n_top = self.config.top_logprobs
        wd = self._watchdog
        wd_limit = self._integrity.logit_limit if wd else 0.0

        def verify(params, cache, counts, tokens, positions, tables, step_ctr,
                   ipack, fpack, wdf=None):
            step_key = jax.random.fold_in(jax.random.PRNGKey(0), step_ctr)
            seeds, topk = ipack[0], ipack[1]
            temp, topp, freqp, presp = fpack[0], fpack[1], fpack[2], fpack[3]
            # KV for every fed position is written by the forward pass;
            # positions past the accepted prefix hold garbage that later
            # dispatches overwrite before any mask lets it be attended
            # (history masks are position-based: pool reads stop below each
            # lane's current position).
            sums = x = None
            if self._own_programs:
                # the module's own program over the [S, K1] fed positions (a
                # module that drafts keeps no state per slot: None). Where it
                # drafts, the raw output goes on to its prediction module
                lanes = jnp.arange(tokens.shape[0], dtype=jnp.int32)
                h, cache, _, sums = self.model.forward_chunk(
                    params, cfg, tokens, positions, cache, tables, None, lanes,
                    **({"raw": True} if self._device_drafts else {}),
                )
                if self._device_drafts:
                    x, h = h, self.model.final_norm(params, cfg, h)
                logits_all = self.model.lm_head(params, cfg, h)
            else:
                h, cache = forward_chunk(
                    params, cfg, tokens, positions, cache, tables,
                    hidden_only=True, with_history=True,
                )
                logits_all = lm_head(params, cfg, h)  # [S, K1, V] f32
            if wd:
                logits_all = jnp.where(
                    wdf > 0, jnp.full_like(logits_all, jnp.nan), logits_all
                )
                bad_pos = (~jnp.all(jnp.isfinite(logits_all), axis=-1)) | (
                    jnp.max(jnp.abs(logits_all), axis=-1) > wd_limit
                )  # [S, K1]
                bad = jnp.any(bad_pos & (positions >= 0), axis=-1)  # [S]
            outs = speculative_targets(
                logits_all, counts, positions >= 0, step_key, seeds,
                temp, topk, topp, freqp, presp,
                with_pen=with_pen, with_sample=with_sample, with_lp=with_lp,
                n_top=n_top,
            )
            *fetch, counts = outs  # (tgt,) or (tgt, lp, tids, tlps)
            if wd:
                fetch[0] = jnp.where(
                    bad[:, None], jnp.int32(WATCHDOG_TOKEN), fetch[0]
                )
            if self._device_drafts:
                # position j's target is the token that follows it: the
                # prediction module's choice after each, [S, K1] (the host
                # keeps the one behind the last accepted token)
                hd, cache, more = self.model.draft_chunk(
                    params, cfg, x, fetch[0], positions, cache, tables,
                )
                drafts = jnp.argmax(
                    self.model.lm_head(params, cfg, hd), axis=-1
                ).astype(jnp.int32)
                return (*fetch, sums + more, drafts, cache, counts)
            if sums is not None:
                return (*fetch, sums, cache, counts)
            return (*fetch, cache, counts)

        return jax.jit(verify, donate_argnums=(1, 2))

    # -- penalty-count buffer -------------------------------------------------

    def _state_args(self, params) -> tuple:
        """The leading arguments of a step program: the weights, the pool,
        and for a module's own programs the slots' state behind it (None
        where the module keeps none)."""
        if self._own_programs:
            return (params, self.cache, self.slot_state)
        return (params, self.cache)

    def _take_state(self, result: tuple) -> tuple:
        """Take the pool (and the slots' state, of a module's own programs)
        a step program handed back, in front of the penalty counts at the
        end of ``result``; what is left is what the host fetches, and the
        counts."""
        if self._own_programs:
            *rest, self.cache, self.slot_state, counts = result
        else:
            *rest, self.cache, counts = result
        return (*rest, counts)

    def _add_model_counters(self, sums) -> None:
        for name, n in zip(self.model.COUNTERS, sums):
            self.model_counters[name] += int(n)

    def _refuse_for_state(self, what: str) -> None:
        """A slot model's pages are half of a request: the slots' recurrent
        state does not travel with them (snapshots at block boundaries are
        ROADMAP M5), so whatever would hand pages over without it is refused
        by name instead of served wrongly."""
        if self._slot_model:
            raise kv_pages.StateNotPortable(
                f"{what}: {type(self.model_config).__name__} keeps state per "
                "slot beside its pages, and that state does not follow pages yet"
            )

    def _slow_fault(self) -> None:
        """The ``slow`` fault action at the engine dispatch point
        (docs/resilience.md §Fail-slow): an injected host-side delay —
        fixed + seeded jitter — right before the jitted call, modelling a
        worker that passes every probe but drags every dispatch (thermal
        throttle, sick NIC, noisy co-tenant). Deliberately independent of
        the straggler/profiling knobs: the chaos gate's *undefended*
        control leg needs the fault to fire with the defense off."""
        if faults_mod.current() is not None:
            d = faults_mod.slow_gate("engine", self._fault_addr)
            if d > 0:
                time.sleep(d)

    def _straggler_tick(self, phase: str, t_step: float, tokens: int) -> None:
        """One dispatch into the fail-slow detector: coarse step-loop wall
        time per token (fed EVERY dispatch, unlike the sampled profiling
        timeline — a differential verdict over peers needs the full
        stream, and two perf_counter reads per dispatch are cheap)."""
        self._straggler.note_dispatch(
            phase, (time.perf_counter() - t_step) * 1e6, tokens
        )

    def _wd_args(self) -> tuple:
        """Extra dispatch args for the output watchdog: empty with the
        integrity plane off (the jitted programs then take exactly the
        pre-integrity signature), else one scalar — 0 normally, 1 when the
        ``poison`` fault action fires for this dispatch (the injected-SDC
        drill: the fn overwrites its logits with NaN in-jit, and the
        watchdog must catch every affected lane before a token escapes).
        The steady-state 0 is uploaded ONCE and reused — the hot path
        must not pay a fresh host→device transfer per dispatch for a drill
        flag."""
        if not self._watchdog:
            return ()
        if faults_mod.current() is not None and faults_mod.poison_gate(
            "engine", self._fault_addr
        ):
            return (self._put(np.int32(1)),)
        wd0 = getattr(self, "_wd_zero", None)
        if wd0 is None:
            wd0 = self._wd_zero = self._put(np.int32(0))
        return (wd0,)

    def _counts_sync_fn(self, rbucket: int, pbucket: int):
        """Tiny jitted reset+rebuild of penalty-count rows. Bucketed shapes
        (powers of two) bound the number of compilations; padded entries use
        row index S, dropped by the scatters."""
        fn = self._counts_sync_fns.get((rbucket, pbucket))
        if fn is None:
            record_compile("counts_sync")

            def sync(counts, reset_rows, add_rows, add_toks):
                counts = counts.at[reset_rows].set(0, mode="drop")
                return counts.at[add_rows, add_toks].add(1, mode="drop")

            fn = self._counts_sync_fns[(rbucket, pbucket)] = jax.jit(
                sync, donate_argnums=(0,)
            )
        return fn

    def _counts_fix_fn(self, pbucket: int):
        """Tiny jitted subtraction of over-added penalty counts. The verify
        scan adds EVERY active position's target into the count buffer
        (sequential exactness up to the first draft mismatch costs pollution
        past it); the host knows exactly which targets were kept, so the
        correction is ≤ spec_k entries per lane per dispatch — never a full
        out_tokens rebuild. Padded entries use row index S, dropped."""
        fn = self._counts_fix_fns.get(pbucket)
        if fn is None:
            record_compile("counts_fix")

            def fix(counts, rows, toks):
                return counts.at[rows, toks].add(-1, mode="drop")

            fn = self._counts_fix_fns[pbucket] = jax.jit(
                fix, donate_argnums=(0,)
            )
        return fn

    def _release_counts(self) -> None:
        """No penalized lane is running: free the [S, V] device buffer and
        the strong _Seq references held by the row tracking. Rebuilt from
        out_tokens on the next penalized admission. Called after a dispatch
        that penalized nothing; while some OTHER lane is penalized (the
        chunk and the decode program of one host step each see only their
        own lanes) the buffer stays, or every host step would rebuild it.
        The multihost leader broadcasts the release, which is what the
        followers drop theirs on."""
        if any(s is not None and s.penalized for s in self._slots):
            return
        if self._counts is not None:
            self._counts = None
            self._counts_lanes = [None] * self.config.max_slots
            if self._dispatch_hook is not None:
                self._dispatch_hook("counts_release", {}, {})

    def _sync_counts(self, lanes: List[Optional["_Seq"]]) -> None:
        """Bring the device count buffer in line with the current lane set:
        rows whose sequence changed since the last penalized dispatch are
        zeroed and rebuilt from that sequence's emitted output tokens (so
        penalties survive preemption and remote prefill). Rows whose lane is
        unchanged were maintained in-jit and are left alone. Rows of
        NON-penalized lanes are skipped entirely — apply_penalties multiplies
        them by zero, so their contents are never read, and rebuilding them
        (potentially thousands of out_tokens across a busy engine) would
        stall every lane the moment the first penalized request lands."""
        S = self.config.max_slots
        if self._counts is None:
            # _put: replicated global array on a process-spanning mesh
            self._counts = self._put(
                np.zeros((S, self.model_config.vocab_size), np.int32)
            )
        changed = [
            i for i in range(S)
            if self._counts_lanes[i] is not lanes[i]
            and lanes[i] is not None and lanes[i].penalized
        ]
        if not changed:
            self._counts_lanes = list(lanes)
            return
        pairs: List[Tuple[int, int]] = []
        for i in changed:
            seq = lanes[i]
            if seq.out_tokens:
                pairs.extend((i, t) for t in seq.out_tokens)
        rb, pb = 1, 1
        while rb < len(changed):
            rb *= 2
        while pb < max(len(pairs), 1):
            pb *= 2
        reset = np.full((rb,), S, np.int32)
        reset[: len(changed)] = changed
        add_rows = np.full((pb,), S, np.int32)
        add_toks = np.zeros((pb,), np.int32)
        for j, (r, t) in enumerate(pairs):
            add_rows[j] = r
            add_toks[j] = t
        if self._dispatch_hook is not None:
            # the sync is itself a device program: followers must run it in
            # the same order as every other dispatch
            self._dispatch_hook(
                "counts", dict(rb=rb, pb=pb),
                dict(reset=reset, add_rows=add_rows, add_toks=add_toks),
            )
        self._counts = self._counts_sync_fn(rb, pb)(
            self._counts, self._put(reset), self._put(add_rows),
            self._put(add_toks),
        )
        self._counts_lanes = list(lanes)

    def warmup(self, variants: str = "all") -> Dict[str, float]:
        """Compile the chunk and decode step functions before serving traffic.

        A cold compile takes seconds per program on the chip (6-9 s each at
        Qwen2.5-1.5B, chip_smoke.py on a v5e) — taken mid-request it stalls
        every in-flight sequence.

        Single-chip engines compile AOT (``jit.lower(shapes).compile()``)
        over abstract shapes — nothing executes, so no donation hazard — and
        the variants compile CONCURRENTLY in a thread pool (XLA releases the
        GIL), cutting first-boot wall time to roughly the slowest single
        program. ``variants="greedy"`` compiles only the greedy-serving
        programs (big-model boots where every extra program costs its
        compile time again); the lp/pen variants stay lazy in every mode
        (rare; first use compiles once). The chunk program is compiled at
        every rung of ``_chunk_rungs``: both history variants at
        ``max_slots`` rows, and under it the greedy history-bearing program
        alone (zero trips of its loop is the no-history case; a sampled
        variant at a small rung compiles at first use, like lp/pen).

        Mesh engines of ``models/llama.py``'s programs keep the executing
        warmup: on a multi-process mesh the warmup executions themselves
        must run in leader/follower lockstep. A module that serves its own
        programs on a mesh compiles ahead as one device does, from avals
        that carry each array's sharding.
        Returns per-variant compile seconds (``setup_phase_s`` has the
        start-up's phases by name)."""
        cfg = self.config
        S, C, MB = cfg.max_slots, cfg.prefill_chunk, cfg.max_blocks_per_seq
        timings: Dict[str, float] = {}
        # start-up's clock (main thread): `compile` is this method's wall,
        # out of which the time under `tracing_turn` goes to `lower`
        setup = profiling_mod.setup_clock(jax.profiler.TraceAnnotation)
        setup.switch(profiling_mod.S_COMPILE)

        def done():
            setup.switch(None)
            self._clock.compile_key = None  # built here, not on the served path
            return timings

        from concurrent.futures import ThreadPoolExecutor

        sample_set = (False,) if variants == "greedy" else (False, True)
        # (rows, want_sample, want_history) of every chunk program to compile
        chunk_set = [
            (S, want_sample, want_history)
            for want_sample in sample_set
            # a module's own chunk program has one form (`_chunk`)
            for want_history in ((True,) if self._own_programs else (False, True))
        ] + [(rows, False, True) for rows in self._chunk_rungs if rows < S]

        def chunk_name(rows, want_sample, want_history):
            at = "" if rows == S else f",rows={rows}"
            return f"chunk(sample={want_sample},history={want_history}{at})"

        def warm_sealing():
            # the take program at every block count _take_sealing pads to
            # (executed: it reads the pool and writes nothing), side by
            # side: each compiles in a third of a second, too short for the
            # persistent cache to keep, so every start pays each again
            if not self._seal_checksums or not self._sealing_sizes:
                return
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=len(self._sealing_sizes)) as takes:
                # dynlint: allow-host-sync(warmup compile barrier, pre-serving)
                jax.block_until_ready(list(takes.map(
                    lambda n: kv_pages.take(self.cache, [0] * n),
                    self._sealing_sizes,
                )))
            timings["take_blocks"] = round(time.perf_counter() - t0, 2)

        if self.mesh is not None and not self._own_programs:
            def packs(rows):
                fpack = np.zeros((4, rows), np.float32)
                fpack[1] = 1.0  # top_p
                return (self._put(np.zeros((2, rows), np.int32)),
                        self._put(fpack))

            ctr = self._put(np.int32(0))
            for rows, want_sample, want_history in chunk_set:
                # every row a padding row: nothing is written, nothing sampled
                t0 = time.perf_counter()
                out, self.cache, self._dummy_counts = self._chunk(
                    False, False, want_sample, want_history, rows
                )(
                    self.params, self.cache, self._dummy_counts,
                    self._put(np.zeros((rows, C), np.int32)),
                    self._put(np.full((rows, C), -1, np.int32)),
                    self._put(np.zeros((rows, MB), np.int32)),
                    self._put(np.full((rows,), -1, np.int32)),
                    self._put(np.full((rows,), S, np.int32)), ctr,
                    *packs(rows),
                )
                # dynlint: allow-host-sync(warmup compile barrier, pre-serving)
                jax.device_get(out)
                timings[chunk_name(rows, want_sample, want_history)] = round(
                    time.perf_counter() - t0, 2
                )
            tables = self._put(np.zeros((S, MB), np.int32))
            svec_i = np.zeros((S,), np.int32)
            ipack, fpack = packs(S)
            for want_sample in sample_set:
                t0 = time.perf_counter()
                out, _, _, self.cache, self._dummy_counts = self._decode(
                    False, False, want_sample
                )(
                    self.params_decode, self.cache, self._dummy_counts,
                    self._put(svec_i), self._put(np.full((S,), -1, np.int32)),
                    tables, ctr, ipack, fpack,
                )
                # dynlint: allow-host-sync(warmup compile barrier, pre-serving)
                jax.device_get(out)
                timings[f"decode(sample={want_sample})"] = round(
                    time.perf_counter() - t0, 2
                )
            setup.switch(profiling_mod.S_SEALING)
            warm_sealing()
            return done()

        # a module that serves on a mesh compiles here too: what lives on
        # the device is described in the sharding it has, what the host
        # makes as whole on every device (one process: `_put` is a plain
        # transfer, and the program takes it where it was compiled to)
        where = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            where = {"sharding": NamedSharding(self.mesh, PartitionSpec())}

        def sd(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, **where)

        def like(a):
            if self.mesh is None:
                return sd(a.shape, a.dtype)
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

        p_sd = jax.tree.map(like, self.params)
        pd_sd = jax.tree.map(like, self.params_decode)
        cache_sd = jax.tree.map(like, self.cache)
        # the pool, and behind it the slots' state of a module's own programs
        pool_sd = (cache_sd,) + ((jax.tree.map(like, self.slot_state),)
                                 if self._own_programs else ())
        counts_sd = jax.tree.map(
            lambda a: sd(a.shape, a.dtype), self._dummy_counts
        )
        tbl = sd((S, MB), jnp.int32)
        ctr = sd((), jnp.int32)
        ip = sd((2, S), jnp.int32)
        fp = sd((4, S), jnp.float32)
        svec = sd((S,), jnp.int32)
        # watchdog variants take one extra scalar (the poison flag)
        wd_tail = (sd((), jnp.int32),) if self._watchdog else ()

        jobs = []
        for rows, want_sample, want_history in chunk_set:
            rvec = sd((rows,), jnp.int32)
            jobs.append((
                chunk_name(rows, want_sample, want_history),
                self._chunk(False, False, want_sample, want_history, rows),
                (p_sd, *pool_sd, counts_sd, sd((rows, C), jnp.int32),
                 sd((rows, C), jnp.int32), sd((rows, MB), jnp.int32), rvec,
                 rvec,
                 # a module that drafts: the token that follows each position
                 *((sd((rows, C), jnp.int32),) if self._device_drafts else ()),
                 ctr, sd((2, rows), jnp.int32),
                 sd((4, rows), jnp.float32)) + wd_tail,
                ("chunk", False, False, want_sample, want_history, rows),
            ))
        for want_sample in sample_set:
            jobs.append((
                f"decode(sample={want_sample})",
                self._decode(False, False, want_sample),
                (pd_sd, *pool_sd, counts_sd, svec, svec, tbl, ctr, ip, fp)
                + wd_tail,
                ("decode", False, False, want_sample),
            ))
            if self._spec_k > 0:
                sk1 = sd((S, self._spec_k + 1), jnp.int32)
                jobs.append((
                    f"verify(sample={want_sample})",
                    self._verify(False, False, want_sample),
                    (pd_sd, cache_sd, counts_sd, sk1, sk1, tbl, ctr, ip, fp)
                    + wd_tail,
                    ("verify", False, False, want_sample),
                ))

        # tracing is Python and holds the interpreter lock, compiling (or
        # loading from the persistent cache) is XLA's and does not: trace in
        # turn, the sampled programs first (theirs is the longest compile),
        # so that each compile starts when its trace ends instead of all of
        # them after all the traces
        tracing_turn = threading.Lock()
        lowering_s = [0.0]
        jobs.sort(key=lambda job: not job[3][3])  # key = (kind, lp, pen, sample, ...)

        def compile_one(job):
            name, fn, args, key = job
            if not hasattr(fn, "lower"):  # already a compiled executable
                return key, fn
            t0 = time.perf_counter()
            with tracing_turn:
                t1 = time.perf_counter()
                lowered = fn.lower(*args)
                lowering_s[0] += time.perf_counter() - t1
            compiled = lowered.compile()
            timings[name] = round(time.perf_counter() - t0, 2)
            return key, compiled

        with ThreadPoolExecutor(max_workers=min(8, len(jobs)) + 1) as ex:
            # the take programs execute while the step programs compile
            sealing = ex.submit(warm_sealing)
            for key, compiled in ex.map(compile_one, jobs):
                # serve straight off the compiled executable
                if key[0] == "chunk":
                    self._chunk_fns[key[1:]] = compiled
                elif key[0] == "verify":
                    self._verify_fns[key[1:]] = compiled
                else:
                    self._decode_fns[key[1:]] = compiled
            sealing.result()
        setup.credit(profiling_mod.S_LOWER, lowering_s[0] * 1e6,
                     out_of=profiling_mod.S_COMPILE)
        # the take programs' own seconds, beside the compiles and not after them
        setup.credit(profiling_mod.S_SEALING, timings.get("take_blocks", 0.0) * 1e6)
        return done()

    # -- AsyncEngine interface ----------------------------------------------

    async def generate(
        self, request: Context[PreprocessedRequest]
    ) -> AsyncIterator[Annotated[dict]]:
        req = request.data
        if not isinstance(req, PreprocessedRequest):
            req = PreprocessedRequest.from_dict(req)
        if len(req.token_ids) > self.config.max_model_len - 1:
            yield Annotated.from_error(
                f"prompt is {len(req.token_ids)} tokens; engine max_model_len "
                f"is {self.config.max_model_len}"
            )
            return
        self._ensure_thread()
        seq = _Seq(request, req, asyncio.get_running_loop())
        if seq.resumed:
            self.resumed_requests += 1
        tenant = getattr(request.context, "tenant", None)
        if self._qos is not None:
            # QoS on: anonymous requests become the shared default tenant
            # (they must not bypass fair queuing / budgets); the class
            # table supplies the eviction level + scheduling weight
            seq.tenant = tenant or qos_mod.DEFAULT_TENANT
            seq.level, seq.weight = self._qos.class_of(seq.tenant)
        elif tenant:
            seq.tenant = tenant  # attribution only (spans, metrics)
        if self._spec_k > 0 and not self._multihost:
            # one suffix index per request (prompt indexed up front, emitted
            # tokens appended as they stream); spec off ⇒ stays None and the
            # step loop never allocates drafter state. Multihost never
            # dispatches verify (followers only replay chunk/decode
            # opcodes), so it must not pay the index either.
            seq.drafter = (
                DeviceDrafter(seq.prompt, self._spec_k)
                if self._device_drafts
                else NgramDrafter(seq.prompt, self._spec_k, self._spec_ngram)
            )
        with self._cond:
            self._pending.append(seq)
            self._cond.notify()

        try:
            while True:
                item = await seq.out_queue.get()
                if item is _FINISHED:
                    return
                yield item
        finally:
            # Consumer closed the stream (stop string hit downstream, client
            # disconnect, GeneratorExit): make sure the engine stops burning
            # the slot. No-op after a normal finish.
            request.context.stop_generating()
            with self._cond:
                self._cond.notify()

    # -- engine thread -------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._step_loop, name="jax-engine-step", daemon=True
            )
            self._thread.start()

    def close(self) -> None:
        with self._cond:
            self._shutdown = True
            self._cond.notify()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._crc_worker is not None:
            self._crc_worker.close()

    def _step_loop(self) -> None:
        clock = self._clock
        clock.start()
        try:
            while not self._host_step(clock):
                pass
        except Exception:
            logger.exception("engine step loop crashed")
            # fail every in-flight request rather than hanging clients
            for seq in list(self._slots) + list(self._pending) + list(self._awaiting.values()):
                if seq is not None:
                    seq.emit(Annotated.from_error("engine internal error"))
                    seq.emit(_FINISHED)

    def _host_step(self, clock) -> bool:
        """One iteration of the engine thread: one tree of phases under one
        ``engine.step`` annotation. True once the engine has shut down."""
        with clock.step(self._step_counter):
            with self._cond:
                while (
                    not self._shutdown
                    and not self._pending
                    and not self._posted
                    and not any(self._slots)
                    and self._inflight is None
                    and not self._pending_spills
                    and self._counts is None  # idle pass frees it first
                    and not self._seal_unsettled()  # and registers these
                ):
                    if self._awaiting or self._staged_migrations:
                        # wake periodically to sweep remote-prefill
                        # timeouts and unclaimed staged migrations
                        with clock(P_WAIT):
                            self._cond.wait(timeout=1.0)
                        break
                    # parking idle: record it, or the last busy beat
                    # would age into a false stall (health.py reads
                    # busy-at-last-beat, and an idle park beats no more)
                    self.heartbeat.beat(busy=False)
                    with clock(P_WAIT):
                        self._cond.wait()
                if self._shutdown:
                    # drain posted callbacks before exiting: callers of
                    # post() (transfer-plane _engine_call) await futures
                    # these resolve — dropping them would hang the
                    # awaiting task forever on a close() race
                    self._run_posted()
                    return True
            # liveness beat BEFORE the work: if the dispatch below (or a
            # posted callback / spill harvest) wedges, the recorded busy
            # flag plus a growing beat age is exactly the stall
            # signature the health monitor detects. Every wake source of
            # the idle-wait predicate above counts as busy — a wedge in
            # a posted callback on an otherwise-idle engine must not
            # masquerade as an idle park.
            self.heartbeat.beat(busy=bool(
                self._pending
                or self._posted
                or self._inflight is not None
                or any(s is not None for s in self._slots)
                or self._awaiting
                or self._pending_spills
            ))
            clock.active = any(self._slots)
            if self._crc_worker is not None and self._crc_worker.has_done:
                with clock(P_SEAL_CRC):  # what it finished, or its failure
                    self._seal_land()
            with clock(P_POSTED):
                self._run_posted()
            with clock(P_SWEEP):
                self._sweep_remote_timeouts()
                self._sweep_staged()
            idle = (
                not self._pending and not any(self._slots)
                and self._inflight is None
            )
            # idle = nothing to stall: drain spills fully so revisits
            # after an idle gap see their prefixes in the host tier,
            # and drop the [S, V] penalty-count buffer (16 MB at a
            # 128k vocab) a final dispatch with penalized lanes left
            # allocated — no later dispatch would ever release it
            with clock(P_SPILLS):
                self._harvest_spills(force=idle)
            if idle:
                self._release_counts()
                if self._perf is not None:
                    # exclude the idle gap from throughput timing
                    self._perf.note_idle()
                if self._fair is not None:
                    # bound fair-queue memory across tenant churn; an
                    # idle engine has no backlog to be fair about
                    self._fair.forget_absent(
                        [s.tenant for s in self._awaiting.values()]
                    )
            with clock(P_ADMIT):
                self._coalesce_admission_wave()
                self._admit()
            clock.active = any(self._slots)
            self._dispatch_step()
            if (
                not any(self._slots) and self._inflight is None
                and self._pending and self._awaiting
            ):
                # every pending request is parked (capacity or shared
                # in-flight prefix) behind remote prefills: poll gently
                # instead of spinning the GIL against the transfer plane
                with self._cond, clock(P_WAIT):
                    self._cond.wait(timeout=0.005)
        return False

    def post(self, fn) -> None:
        """Schedule a host function to run on the engine thread (thread-safe).
        The only way external code may touch the cache or allocator. After
        close(), the fn runs INLINE on the caller thread: the step thread's
        shutdown drain only covers callbacks it observed, and a post racing
        the drain would otherwise never run — hanging any _engine_call
        future awaiting it."""
        with self._cond:
            if not self._shutdown:
                self._ensure_thread()
                self._posted.append(fn)
                self._cond.notify()
                return
        # inline path: serialize against the step thread's shutdown drain and
        # any other post-close caller — two teardown threads (e.g. concurrent
        # transfer-plane _engine_calls) must not mutate allocator/cache state
        # concurrently when the engine thread no longer serializes them
        with self._posted_exec_lock:
            fn()

    def _run_posted(self) -> None:
        while True:
            with self._cond:
                if not self._posted:
                    return
                fn = self._posted.popleft()
            with self._posted_exec_lock:
                fn()

    # -- scheduling ----------------------------------------------------------

    def _coalesce_admission_wave(self) -> None:
        """Hold the first dispatch briefly while an admission wave is still
        landing (engine idle, pending requests growing, free slots left), so
        the whole wave prefills together. Without this, whichever requests
        happen to be queued when the engine thread first wakes prefill alone
        and every straggler's TTFT grows by a full extra chunk dispatch."""
        window = self.config.admission_window
        if window <= 0:
            return
        if self._inflight is not None or any(s is not None for s in self._slots):
            return  # engine busy: dispatch cadence already set by compute
        deadline = time.perf_counter() + window
        with self._cond:
            prev = len(self._pending)
            while (
                0 < prev < self.config.max_slots
                and not self._shutdown
                and time.perf_counter() < deadline
            ):
                self._cond.wait(timeout=0.001)
                if len(self._pending) == prev:
                    return  # wave stopped growing
                prev = len(self._pending)

    def _admit(self) -> None:
        """Move pending requests into free slots; run their prefill."""
        deferred: List[_Seq] = []  # waiting on another lane's in-flight prefix
        try:
            self._admit_inner(deferred)
        finally:
            if deferred:
                with self._cond:
                    for s in reversed(deferred):
                        self._pending.appendleft(s)

    def _pop_pending_locked(self) -> "_Seq":
        """Next pending request to consider. FIFO on the single-tenant
        path; with QoS on, weighted-fair: the request whose tenant has
        the smallest virtual time (most starved by weighted share) wins,
        FIFO within a tenant — a noisy neighbor's deep backlog cannot
        starve a light tenant's next request. Caller holds ``_cond``."""
        if self._fair is None or len(self._pending) <= 1:
            return self._pending.popleft()
        i = self._fair.pick([s.tenant for s in self._pending])
        if i == 0:
            return self._pending.popleft()
        seq = self._pending[i]
        del self._pending[i]
        return seq

    def _tenant_contended(self, tenant: str) -> bool:
        """Is any OTHER tenant actively HOLDING engine resources (a slot
        or a remote-prefill allocation)? KV budgets are work-conserving:
        they bind only under contention — a tenant alone on the chip may
        use the whole pool. Deliberately NOT counting merely-pending
        tenants: two over-budget tenants whose only contention is each
        other's queued request would otherwise defer each other forever
        on an empty engine (each admits here; the class-aware preemption
        path still reclaims from whichever overruns once both run)."""
        if any(
            s is not None and s.tenant != tenant for s in self._slots
        ):
            return True
        return any(s.tenant != tenant for s in self._awaiting.values())

    def _kv_budget_defers(self, seq: "_Seq") -> bool:
        """Admission-side KV budget: defer a tenant already holding (or
        about to exceed) its pool share while other tenants are active."""
        if self._tenant_kv_budget <= 0 or not seq.tenant:
            return False
        need = self.allocator.blocks_needed(len(seq.prompt))
        held = self.allocator.tenant_blocks.get(seq.tenant, 0)
        if held + need <= self._tenant_kv_budget:
            return False
        return self._tenant_contended(seq.tenant)

    def _slot_budget_defers(self, seq: "_Seq") -> bool:
        """Admission-side slot budget (docs/qos.md): a tenant already
        occupying its share of the decode batch defers while any OTHER
        tenant is actively holding resources — concurrency isolation with
        the same work-conserving contract as the KV budget (an uncontended
        tenant may fill every slot)."""
        if self._tenant_slot_budget <= 0 or not seq.tenant:
            return False
        held = sum(
            1 for s in self._slots
            if s is not None and s.tenant == seq.tenant
        )
        if held < self._tenant_slot_budget:
            return False
        return self._tenant_contended(seq.tenant)

    def _budget_denies_grow(self, seq: "_Seq", n_tokens: int) -> bool:
        """Decode-growth KV budget: an over-share tenant's sequence is
        recompute-preempted (it pays with its own latency) instead of
        squeezing other tenants out of the pool."""
        if self._tenant_kv_budget <= 0 or not seq.tenant or seq.alloc is None:
            return False
        extra = self.allocator.blocks_needed(
            min(n_tokens, self.config.max_model_len)
        ) - len(seq.alloc.block_ids)
        if extra <= 0:
            return False
        held = self.allocator.tenant_blocks.get(seq.tenant, 0)
        if held + extra <= self._tenant_kv_budget:
            return False
        return self._tenant_contended(seq.tenant)

    def _preempt_victim_for(self, seq: "_Seq") -> "_Seq":
        """Class-aware preemption: when ``seq`` needs blocks the pool
        can't yield, prefer preempting an active sequence of a LOWER
        class (or of a tenant over its KV budget) — lowest level first,
        most blocks held within a level. Falls back to ``seq`` itself
        (the pre-QoS behavior) when no better victim exists. The
        reclaimable tier is already class-ordered in the allocator; this
        extends the same order to hard-held blocks."""
        if self._fair is None:
            return seq
        best = None
        for s in self._slots:
            if s is None or s is seq or s.tenant == seq.tenant or s.alloc is None:
                continue
            over = (
                self._tenant_kv_budget > 0
                and self.allocator.tenant_blocks.get(s.tenant, 0)
                > self._tenant_kv_budget
            )
            if s.level < seq.level or over:
                key = (s.level, -len(s.alloc.block_ids))
                if best is None or key < best[0]:
                    best = (key, s)
        return best[1] if best is not None else seq

    def _admit_inner(self, deferred: List["_Seq"]) -> None:
        while True:
            with self._cond:
                if not self._pending:
                    return
                free = [i for i, s in enumerate(self._slots) if s is None]
                if not free:
                    return
                seq = self._pop_pending_locked()
            if seq.ctx.context.is_stopped:
                if seq.alloc is not None:
                    self.allocator.free_sequence(seq.alloc)
                    seq.alloc = None
                seq.emit(Annotated.from_data(LLMEngineOutput.final(FinishReason.CANCELLED).to_dict()))
                seq.emit(_FINISHED)
                continue
            if seq.alloc is None and getattr(seq.request, "migrate", None):
                # re-homed migrated stream: adopt the staged allocation
                # (cached_tokens = N-1 ⇒ the prefill below computes exactly
                # one fresh position). Miss/mismatch falls through to the
                # ordinary resume recompute.
                self._adopt_staged(seq)
            if seq.alloc is not None and seq.generated:
                # remotely-prefilled sequence re-entering for a decode slot:
                # KV + first token already landed, just start decoding
                seq.slot = free[0]
                self._slots[seq.slot] = seq
                self._note_admitted(seq)
                continue
            if seq.alloc is not None:
                # remote prefill failed/timed out: run the prefill locally on
                # the allocation we already hold
                seq.slot = free[0]
                self._slots[seq.slot] = seq
                self._note_admitted(seq)
                seq.prefill_pos = min(seq.alloc.cached_tokens, len(seq.prompt) - 1)
                continue
            if seq.wait_hash is not None:
                if self.allocator.inflight_pending(seq.wait_hash):
                    # still parked on another lane's in-flight prefix: skip
                    # the full re-probe (an O(prompt) hash walk per loop
                    # iteration that would also inflate probe metrics)
                    deferred.append(seq)
                    continue
                seq.wait_hash = None
            if self._fair is not None and (
                self._kv_budget_defers(seq) or self._slot_budget_defers(seq)
            ):
                # tenant over its KV or slot share while others are active:
                # park this request (its own latency pays) — the scheduler
                # keeps admitting other tenants past it
                deferred.append(seq)
                continue
            alloc = self._alloc_seq(seq)
            if isinstance(alloc, InflightPrefix):
                # another lane is prefilling this prompt's prefix right now:
                # park until it seals (then these become ordinary prefix
                # hits) instead of computing the same blocks twice. Other
                # pending requests keep admitting past this one.
                seq.joined_inflight = True
                seq.wait_hash = alloc.seq_hash
                deferred.append(seq)
                continue
            if alloc is None and (self._inflight is not None or self._zombie_allocs):
                # blocks may be parked behind the in-flight speculative chunk
                self._drain_inflight()
                alloc = self._alloc_seq(seq)
                if isinstance(alloc, InflightPrefix):
                    seq.joined_inflight = True
                    seq.wait_hash = alloc.seq_hash
                    deferred.append(seq)
                    continue
            if alloc is None and self._fair is not None:
                # class-aware preemption: reclaim from a lower-class (or
                # over-budget) tenant's active sequence before giving up.
                # The in-flight chunk is drained first so freed pages can't
                # still receive its speculative writes.
                victim = self._preempt_victim_for(seq)
                if victim is not seq:
                    self._drain_inflight()
                    self._preempt(victim)
                    alloc = self._alloc_seq(seq)
                    if isinstance(alloc, InflightPrefix):
                        seq.joined_inflight = True
                        seq.wait_hash = alloc.seq_hash
                        deferred.append(seq)
                        continue
            if alloc is None:
                if not any(self._slots) and not self._awaiting:
                    # nothing running (or awaiting remote prefill) will ever
                    # free blocks: impossible request
                    seq.emit(Annotated.from_error(
                        f"prompt needs {self.allocator.blocks_needed(len(seq.prompt))} "
                        f"KV blocks; pool has {self.num_blocks}"
                    ))
                    seq.emit(_FINISHED)
                    continue
                with self._cond:
                    self._pending.appendleft(seq)  # retry when blocks free up
                return
            seq.alloc = alloc
            if seq.resumed:
                # the chaos-gate observable (docs/resilience.md §Live
                # migration): positions of another worker's dead stream this
                # admission recomputes. The last position is excluded — it
                # was never computed anywhere (the source sampled its token
                # but hadn't fed it). A migrate-adopted admission never
                # reaches this line (its staged alloc covers everything).
                self.resume_recompute_tokens += max(
                    len(seq.prompt) - alloc.cached_tokens - 1, 0
                )
            if seq.joined_inflight:
                # telemetry: tokens this request got for free by waiting for
                # a concurrent identical prefix instead of recomputing it
                self.allocator.shared_prefill_tokens += alloc.cached_tokens
                seq.joined_inflight = False
            if alloc.host_hits:
                # must land before ANY path uses the allocation: both local
                # prefill and remote-prefill submission treat cached_tokens
                # (which counts host hits) as valid device KV
                self._inject_host_hits(alloc)
            if seq.emitted == 0:  # don't re-count preempted re-admissions
                self.total_requests += 1
                self.total_prompt_tokens += len(seq.prompt)

            # conditional disaggregation: long-enough prefills (minus whatever
            # the prefix cache already covers) go to a remote prefill worker
            policy = self._remote_policy
            uncached = len(seq.prompt) - alloc.cached_tokens
            if (
                policy is not None
                and not seq.remote
                and policy.should_remote(uncached)
            ):
                seq.remote = True
                seq.remote_deadline = time.perf_counter() + self.config.remote_prefill_timeout
                self._awaiting[seq.ctx.id] = seq
                first_suffix_block = alloc.cached_tokens // self.config.kv_block_size
                # trace context rides the prefill request so the remote
                # worker's spans join THIS request's trace (one trace across
                # disaggregated prefill/decode)
                tp = (
                    tracing.format_traceparent(seq.ctx.context.trace)
                    if tracing.enabled() else None
                )
                policy.submit(
                    request_id=seq.ctx.id,
                    token_ids=seq.prompt,
                    block_ids=list(alloc.block_ids[first_suffix_block:]),
                    cached_tokens=alloc.cached_tokens,
                    sampling={
                        "temperature": seq.temperature, "top_k": seq.top_k,
                        "top_p": seq.top_p, "seed": seq.seed,
                    },
                    traceparent=tp or "",
                    # pages backing the cached prefix: the prefill worker
                    # reads these (transfer-plane read_blocks) instead of
                    # recomputing the shared history
                    prefix_block_ids=list(alloc.block_ids[:first_suffix_block]),
                )
                continue  # holds no slot while prefill runs remotely

            seq.slot = free[0]
            self._slots[seq.slot] = seq
            self._note_admitted(seq)
            # the last prompt token is never cached (allocator guarantees it),
            # so every admitted sequence computes at least one position
            seq.prefill_pos = seq.alloc.cached_tokens

    def _note_admitted(self, seq: "_Seq") -> None:
        """A request takes a slot: its wait in the queue ends here, once."""
        if seq.admit_t is None:
            seq.admit_t = time.perf_counter()
            self.queue_wait_us_sum += (seq.admit_t - seq.enqueue_t) * 1e6
            self.queue_wait_count += 1

    def _alloc_seq(self, seq: "_Seq"):
        # a slot model takes no prefix hit: the pages would come without the
        # slot's state, so it prefills from position 0 (`_refuse_for_state`)
        with self._clock(P_ALLOC):
            alloc = self.allocator.allocate_sequence(
                seq.prompt, tenant=seq.tenant, level=seq.level,
                reuse=not self._slot_model,
            )
        # None (no room) and an InflightPrefix (wait for it) decline nothing
        if getattr(alloc, "declined_tokens", 0):
            self.prefix_hits_declined += 1
            seq.prefix_declined = alloc.declined_tokens
        return alloc

    def _note_dispatch(self, phase: str, batch: int, tokens: int) -> None:
        """One sampled dispatch into the timeline (DYN_TPU_PROFILE), from the
        phase clock's counters of this host step: build / device as the host
        observed it / emit, the allocator's share, queue depths, and the PR5
        request/trace ids riding the batch."""
        clock, kind = self._clock, 0 if phase == "chunk" else 1
        reqs: List[str] = []
        traces: List[str] = []
        for s in self._slots:
            if s is None or len(reqs) >= 8:
                continue
            reqs.append(str(s.ctx.id))
            tr = getattr(s.ctx.context, "trace", None)
            tid = getattr(tr, "trace_id", None)
            if tid:
                traces.append(str(tid))
        # epoch-align the perf_counter anchors so captures from different
        # workers merge onto one Perfetto timeline
        now_wall = time.time()  # dynlint: allow-wall-clock(cross-process trace alignment)
        self._timeline.note_dispatch(
            phase,
            ts=now_wall - (time.perf_counter() - clock.t_step),
            step=self._step_counter,
            batch=batch,
            tokens=tokens,
            host_us=clock.step_us(P_DECODE_BUILD if kind else P_CHUNK_BUILD),
            device_us=clock.device_us[kind],
            post_us=clock.step_us(P_DECODE_EMIT if kind else P_CHUNK_EMIT),
            alloc_us=clock.step_us(P_ALLOC) + clock.step_us(P_SEAL_CRC),
            queue=len(self._pending) + len(self._awaiting),
            reqs=reqs,
            traces=traces,
        )

    def _dispatch_step(self) -> None:
        if (
            self._inflight is not None and self._carry_is_stale()
            and not any(s is not None and s.prefill_pos is not None for s in self._slots)
        ):
            # A lane left the decode set and none prefills: the next decode
            # dispatch is built on the host once what is in flight has been
            # read (`_decode_build`), a wait of most of a dispatch. The caller
            # whose stream just ended sends its next request during that
            # wait: read first and admit again, so the request prefills
            # behind this wait and not a dispatch later. (While the seal-time
            # checksum ran on this thread it held the step that long, and the
            # admission at the top of the next step found the request.)
            self._drain_inflight()
            with self._clock(P_ADMIT):
                self._admit()
            self._clock.active = any(self._slots)
        active = [s for s in self._slots if s is not None]
        if not active:
            self._prefill_debt = 0.0  # contention episode over
            self._drain_inflight()
            self._seal_await()  # a quiet engine's registry is whole
            return
        prefilling = any(s.prefill_pos is not None for s in active)
        if not prefilling and self._prefill_debt:
            # debt is only meaningful WITHIN one prefill/decode contention
            # episode: once no lane is prefilling, drop it — a prompt
            # arriving minutes later must not inherit a finished prompt's
            # debt as extra TTFT
            self._prefill_debt = 0.0
        if (
            prefilling
            and self._prefill_budget > 0
            and any(s.prefill_pos is None for s in active)
        ):
            # duty-cycled interleave (DYN_TPU_PREFILL_BUDGET, docs/qos.md):
            # every host step earns `budget` tokens of prefill credit; a
            # chunk dispatch spends what it consumed. While in debt, prefill
            # lanes sit the step out and the decode program runs alone: on
            # average at most `budget` prefill tokens ride each host step,
            # so a long prompt stretches its OWN TTFT and not the decode
            # lanes' wait for the chip. (The knob dates from a chunk
            # dispatch that cost full [S, C] compute and carried the decode
            # lanes one token forward; a chunk dispatch now costs its
            # prefilling rows and the decode lanes run beside it, so there
            # is less left for it to protect: ROADMAP D6.)
            # Idle decode ⇒ this path never taken: prefill at full speed.
            self._prefill_debt = max(
                self._prefill_debt - self._prefill_budget, 0.0
            )
            if self._prefill_debt > 0:
                self._decode_step()
                return
            self._prefill_step(paced=True)
            return
        if prefilling:
            self._prefill_step()
        elif (
            self._spec_k > 0
            and self._dispatch_hook is None
            and not self._multihost
            and any(
                s.drafter is not None and s.drafter.would_draft()
                for s in active
            )
        ):
            # all lanes decoding and at least one drafter's index holds a
            # usable match (would_draft: dormancy + a pre-drain probe of
            # the suffix index — a verify dispatch costs a pipeline drain,
            # so lanes that can't possibly propose must not pay it): try a
            # verify dispatch (it still falls back to the plain pipelined
            # decode step when, after draining, no lane actually drafts).
            # Multihost followers only replay chunk/decode opcodes, so the
            # leader keeps speculation off on a process-spanning mesh.
            self._verify_step()
        else:
            self._decode_step()

    def _prefill_step(self, paced: bool = False) -> None:
        """One host step in which some lane prefills. A chunk dispatch holds
        the rows of the lanes that prefill, one a lane or as many as its
        prompt needs (`chunk_rows_of`); a lane that decodes is never a row
        of it and advances through the decode program in the same step. The
        chunk goes first (a first token is what a caller waits for), and
        both programs are dispatched before either result is fetched, so
        the step leaves the device idle once and not twice; the results are
        read in the device's order, the displaced decode dispatch's before
        the chunk's. (Where `_rides`,
        the lanes that decode are rows of the chunk dispatch instead and the
        decode program sits the step out.)

        ``paced`` (the prefill-budget duty cycle, _dispatch_step): total
        prefill consumption is capped at ONE chunk, handed to the
        most-starved tenant's lanes first, and the consumed tokens are
        charged to the prefill debt that keeps the following steps
        pure-decode."""
        t_step = time.perf_counter() if self._straggler is not None else 0.0
        if self._rides:
            # a riding lane's row starts from its last token, host-side
            self._drain_inflight()
        # cancellations and the decode lanes' growth first: either may free a
        # lane's blocks (a preemption's victim can be a prefilling lane),
        # which no dispatched program may still write
        self._prepare_lanes()
        if not any(self._slots):
            return
        chunk = self._chunk_dispatch(paced, t_step)
        decode = (
            None if self._rides and chunk is not None
            else self._decode_dispatch()
        )
        # results in the order the device gives them: the decode dispatch
        # displaced ran before this step's chunk, and a lane that ends in it
        # frees its slot (and its caller sends the next request) while the
        # chunk is still waited for
        prev = decode[0] if decode is not None else None
        if prev is not None:
            self._process_chunk(prev, defer_free=True)
        if chunk is not None:
            self._clock.steps[0] += 1
            self._chunk_finish(chunk)
        elif decode is not None:
            self._clock.steps[1] += 1

    def _chunk_dispatch(self, paced: bool, t_step: float) -> Optional[_ChunkInflight]:
        """Build and dispatch one [rows, prefill_chunk] program over the lanes
        that prefill, packed to the front; ``rows`` is a rung of
        ``_chunk_rungs``, the rows no lane fills padding (positions -1, as
        an empty lane has). A prefilling lane consumes up to a chunk of
        prompt a row, and where `_lane_rows` as many rows as its prompt needs
        and the rung holds (`chunk_rows_of`: a lane's pieces in consecutive
        rows, in order); at the full width the rows its lanes' first pieces
        leave where `_top_takes_rows` (the module's full-width program is
        the one that reads the rows' lanes) and one row otherwise; under
        pacing and on every other engine one row, so a whole admission wave
        prefills in ceil(longest_suffix / chunk) dispatches. Where `_rides`, the lanes
        that decode are rows too, one token each. Returns the dispatch for
        `_chunk_finish`, or None when no lane takes a prompt token (all
        budgeted out)."""
        with self._clock(P_CHUNK_BUILD):
            built = self._chunk_build(paced)
        if built is None:
            return None
        fn, args, want_pen, fed, filled = built
        tl, clock = self._timeline, self._clock
        with clock(P_CHUNK_DISPATCH if clock.compile_key is None else P_COMPILE):
            *fetch, counts_out = self._take_state(fn(*args))
            clock.dispatched(0)
        # copy_to_host_async right after dispatch: started here, the
        # device→host copy overlaps the chunk's own compute instead of
        # starting cold at get time (the saving is not measured on the
        # current machine)
        for arr in fetch:
            arr.copy_to_host_async()
        sealing = self._take_sealing(filled)
        # the counts go on now, not when the result is fetched: the decode
        # program of this host step takes them next
        if want_pen:
            self._counts = counts_out
        else:
            self._dummy_counts = counts_out
            self._release_counts()
        return _ChunkInflight(
            tuple(fetch), fed, sealing, t_step, tl is not None and tl.should_sample()
        )

    def _chunk_build(self, paced: bool):
        """The host's half of `_chunk_dispatch`: the program, its arguments
        (host arrays put on the device) and what `_chunk_finish` needs; None
        when no lane takes a prompt token."""
        cfg = self.config
        S, C, MB = cfg.max_slots, cfg.prefill_chunk, cfg.max_blocks_per_seq
        pre = [
            i for i in range(S)
            if self._slots[i] is not None
            and self._slots[i].prefill_pos is not None
        ]
        # paced dispatch (prefill-budget duty cycle): one chunk's worth of
        # prefill total this dispatch, most-starved tenant's lanes first —
        # fairness decides WHOSE long prompt advances beside the decode
        # lanes. allow=None is the unpaced fast path.
        allow: Optional[Dict[int, int]] = None
        if paced and pre:
            if self._fair is not None and len(pre) > 1:
                pre.sort(key=lambda i: self._fair.vt(self._slots[i].tenant))
            rem = [
                len(self._slots[i].prompt) - self._slots[i].prefill_pos
                for i in pre
            ]
            allow = dict(zip(pre, qos_mod.split_prefill_budget(rem, C, C)))
        # a row: (lane, its first position, prompt tokens it feeds)
        take: List[Tuple[int, int, int]] = []
        for i in sorted(pre):
            seq = self._slots[i]
            n = min(C, len(seq.prompt) - seq.prefill_pos)
            if allow is not None:
                n = min(n, allow.get(i, 0))
            if n > 0:  # else budgeted out of this step; advances next one
                take.append((i, seq.prefill_pos, n))
        if not take:
            return None
        n_lanes = len(take)
        if self._lane_rows and allow is None:
            # a lane takes the rows its prompt needs, as far as a rung under
            # the full width holds them, or the spare rows of the full width
            # where its program takes them (`chunk_rows_of`): its pieces in
            # consecutive rows, in order, each full but the last
            fed_lanes = [self._slots[i] for i, _, _ in take]
            takes = chunk_rows_of(
                [-(-(len(s.prompt) - s.prefill_pos) // C) for s in fed_lanes],
                [s.enqueue_t for s in fed_lanes], self._chunk_rungs,
                self._top_takes_rows, self._lane_rows_most,
            )
            take = [
                (i, at, min(C, len(s.prompt) - at))
                for (i, start, _), s, k in zip(take, fed_lanes, takes)
                for at in range(start, start + k * C, C)
            ]
        n_prefill = sum(n for _, _, n in take)
        if self._rides:
            # (lane, its place, 0): a decode lane rides along, one token forward
            take = sorted(take + [
                (i, s.total_len - 1, 0) for i, s in enumerate(self._slots)
                if s is not None and s.prefill_pos is None
            ])

        rows = next(r for r in self._chunk_rungs if r >= len(take))
        tokens = np.zeros((rows, C), np.int32)
        positions = np.full((rows, C), -1, np.int32)
        tables = np.zeros((rows, MB), np.int32)
        sample_at = np.full((rows,), -1, np.int32)
        lanes = np.full((rows,), S, np.int32)  # S = a padding row
        ipack_np = np.zeros((2, rows), np.int32)  # seeds, topk
        fpack_np = np.zeros((4, rows), np.float32)  # temp, topp, freqp, presp
        fpack_np[1] = 1.0
        # for a module that drafts: the token that follows each position (the
        # program puts the sampled one behind a prompt's last)
        following = np.zeros((rows, C), np.int32) if self._device_drafts else None
        fed: List[Tuple[int, _Seq, List[int]]] = []  # lane, seq, its tokens
        filled: List[int] = []  # blocks this dispatch fills: they seal at its finish
        for r, (i, start, n) in enumerate(take):
            seq = self._slots[i]
            lanes[r] = i
            tables[r, : len(seq.alloc.block_ids)] = seq.alloc.block_ids
            ipack_np[:, r] = (seq.seed & 0x7FFFFFFF, seq.top_k)
            fpack_np[:, r] = (
                seq.temperature, seq.top_p, seq.freq_pen, seq.pres_pen
            )
            if n == 0:  # a riding decode lane: its last token, at its place
                n = 1
                chunk_toks = [seq.generated[-1] if seq.generated else seq.prompt[-1]]
                sample_at[r] = 0
            else:
                chunk_toks = seq.prompt[start : start + n]
                if start + n == len(seq.prompt):  # a lane's last row alone
                    sample_at[r] = n - 1
            filled += self._blocks_filled(seq.alloc, start, n)
            tokens[r, :n] = chunk_toks
            positions[r, :n] = np.arange(start, start + n)
            if following is not None:
                after = seq.prompt[start + 1 : start + n + 1]
                following[r, : len(after)] = after
            fed.append((i, seq, chunk_toks))
        has_decode = any(
            s is not None and s.prefill_pos is None for s in self._slots
        )
        if has_decode and n_prefill > self.prefill_interleave_max:
            # interleaving bound: the most prefill work any host step ever
            # put beside a live decode lane (the ITL-isolation tests assert
            # it stays ≤ one chunk under pacing, vs the full prompt on the
            # unbudgeted control leg)
            self.prefill_interleave_max = n_prefill
        if paced and has_decode:
            self._prefill_debt += n_prefill
        self.chunk_positions_dispatched += rows * C
        self.chunk_tokens_fed += n_prefill + sum(1 for _, _, n in take if n == 0)
        self.chunk_rows_dispatched += rows
        self.chunk_rows_live += len(take)
        self.chunk_lanes_fed += len({i for i, _, _ in take})
        self.prompt_dispatches += n_lanes
        self.chunk_dispatches_by_rows[rows] = (
            self.chunk_dispatches_by_rows.get(rows, 0) + 1
        )

        self._step_counter += 1
        seqs = [seq for _, seq, _ in fed]
        want_lp = any(s.logprobs is not None for s in seqs)
        want_pen = any(s.penalized for s in seqs)
        want_sample = any(s.temperature > 0.0 for s in seqs)
        # a fresh admission wave's first chunk (every row starting at
        # position 0) attends nothing in the pool: compile out the history
        # gather + partial — this is THE TTFT-critical dispatch. Only the
        # full width has that program; under it, zero trips of the history
        # loop are the no-history case.
        want_history = rows < S or any(
            s.prefill_pos is None or s.prefill_pos > 0 for s in seqs
        )
        if want_history and self._pp == 1 and self._sp == 1:
            bs = cfg.kv_block_size
            # what the chunk program reads of the tables is its module's to
            # say: the tiles up to the longest history, or every table whole
            self.chunk_history_tiles_read += int(
                self.model.chunk_history_tiles(
                    positions, bs, MB, *((lanes,) if self._lane_rows else ())
                )
            )
            self.chunk_history_tiles_full += history_tiles_full(bs, MB)
        if want_pen:
            self._sync_counts(list(self._slots))
        counts_in = self._counts if want_pen else self._dummy_counts
        if self._dispatch_hook is not None:
            # multihost leader: followers run the SAME dispatch in lockstep
            self._dispatch_hook(
                "chunk",
                dict(lp=want_lp, pen=want_pen, sample=want_sample,
                     history=want_history, step=self._step_counter),
                dict(tokens=tokens, positions=positions, tables=tables,
                     sample_at=sample_at, lanes=lanes, ipack=ipack_np,
                     fpack=fpack_np),
            )
        args = self._state_args(self.params) + (
            counts_in, self._put(tokens),
            self._put(positions), self._put(tables), self._put(sample_at),
            self._put(lanes),
            *((self._put(following),) if following is not None else ()),
            self._put(np.int32(self._step_counter)),
            self._put(ipack_np), self._put(fpack_np),
        ) + self._wd_args()
        self._slow_fault()
        fn = self._chunk(want_lp, want_pen, want_sample, want_history, rows)
        return fn, args, want_pen, fed, filled

    def _chunk_finish(self, chunk: _ChunkInflight) -> None:
        """Fetch a chunk dispatch's sampled tokens and hand each row's back
        to its lane: seal what it fed, and where the prompt is through, emit
        the first token. A lane that finishes here was inert in every decode
        dispatch still in flight, so its blocks are free at once."""
        clock = self._clock
        with clock(P_CHUNK_FETCH):
            # dynlint: allow-host-sync(leader sync: one fetch per chunk dispatch,
            # overlapped by copy_to_host_async at dispatch)
            fetched = jax.device_get(chunk.fetch)
            clock.fetched(0)
        with clock(P_CHUNK_EMIT):
            self._chunk_emit(chunk, fetched)
        n_tokens = sum(len(toks) for _, _, toks in chunk.rows)
        if chunk.prof:
            self._note_dispatch("chunk", len(chunk.rows), n_tokens)
        if self._straggler is not None:
            self._straggler_tick("chunk", chunk.t_step, n_tokens)

    def _chunk_emit(self, chunk: _ChunkInflight, fetched) -> None:
        drafts = None
        if self._device_drafts:
            *fetched, drafts = fetched
        if self._own_programs:
            *fetched, sums = fetched
            self._add_model_counters(sums)
        sampled_np = fetched[0]
        lp_np, tids_np, tlps_np = fetched[1:] if len(fetched) > 1 else (None,) * 3
        self._sealing = chunk.sealing
        for r, (lane, seq, toks) in enumerate(chunk.rows):
            if seq.slot != lane:
                continue  # left its lane since the dispatch
            self.allocator.note_tokens_computed(seq.alloc, toks)
            tok = int(sampled_np[r])
            lpinfo = (
                (float(lp_np[r]), tids_np[r], tlps_np[r])
                if lp_np is not None else None
            )
            if seq.prefill_pos is None:  # rode along (`_rides`)
                if self._watchdog and tok < 0:
                    self._watchdog_trip(seq)
                else:
                    self._emit_token(seq, tok, lpinfo=lpinfo)
                continue
            if self._fair is not None and seq.tenant:
                # prefill progress bills the tenant's virtual clock
                # (decode tokens bill in _emit_token/_emit_token_run)
                self._fair.charge(seq.tenant, len(toks), seq.weight)
            seq.prefill_pos += len(toks)
            if seq.prefill_pos < len(seq.prompt):
                continue
            if self._watchdog and tok < 0:
                # watchdog sentinel on the lane's FIRST token: no token has
                # reached the client yet, but the stream still ends typed +
                # in-band so the caller re-homes
                self._watchdog_trip(seq)
                continue
            seq.prefill_pos = None
            self.prompts_prefilled += 1
            seq.first_token_t = time.perf_counter()
            self._emit_token(seq, tok, lpinfo=lpinfo)
            if drafts is not None and seq.drafter is not None:
                # the prediction module's choice for the token after ``tok``
                seq.drafter.offer(int(drafts[r]), seq.total_len)
        self._sealing = None

    def _prepare_lanes(self) -> None:
        """Before a host step dispatches anything: end the cancelled lanes
        and grow the decode lanes' allocations. Either can free blocks (a
        cancellation's, a preemption victim's), so whatever is in flight is
        drained first wherever that happens."""
        with self._clock(P_PREPARE):
            self._end_stopped_and_grow()

    def _end_stopped_and_grow(self) -> None:
        cfg = self.config
        k = cfg.decode_steps
        stopped = [s for s in self._slots if s is not None and s.ctx.context.is_stopped]
        if stopped:
            self._drain_inflight()
            for seq in stopped:
                if seq.slot is not None:
                    self._finish(seq, FinishReason.CANCELLED)

        # capacity: this chunk writes positions total_len-1 .. total_len-2+k,
        # and the next (speculative) chunk another k past that. Prefilling
        # lanes hold their whole prompt's blocks from admission and are no
        # row of the decode program: they neither grow nor dispatch there.
        while True:
            ok = True
            for seq in [s for s in self._slots if s is not None]:
                if seq.prefill_pos is not None:
                    continue
                need = min(seq.total_len - 1 + 2 * k, cfg.max_model_len)
                denied = (
                    self._fair is not None
                    and self._budget_denies_grow(seq, need)
                )
                if denied or not self.allocator.grow(seq.alloc, need):
                    if self._inflight is not None or self._zombie_allocs:
                        self._drain_inflight()  # releases zombie blocks
                    elif denied:
                        # tenant over its KV share under contention: its
                        # own sequence recompute-preempts (isolation —
                        # the overrun pays, not the neighbors)
                        self._preempt(seq)
                    else:
                        # class-aware: reclaim from a lower-class or
                        # over-budget tenant first; falls back to seq
                        self._preempt(self._preempt_victim_for(seq))
                    ok = False
                    break
            if ok:
                break

    def _live_lanes(self) -> List[Optional["_Seq"]]:
        """By slot, the sequences the decode program advances: a prefilling
        lane is none of them (position -1 keeps it inert in-jit)."""
        return [
            s if s is not None and s.prefill_pos is None else None
            for s in self._slots
        ]

    def _carry_is_stale(self) -> bool:
        """Has the live set changed since the in-flight decode dispatch (a
        lane left, or one finished its prefill: the same _Seq, but inert in
        that dispatch's carry)?"""
        return any(
            a is not b for a, b in zip(self._inflight.lanes, self._live_lanes())
        )

    def _decode_step(self) -> None:
        """Pipelined decode: dispatch chunk N+1 off the previous dispatch's
        device-resident carry, THEN fetch + process chunk N. The host↔device
        round trip and the host-side token processing overlap the next
        chunk's execution. Blocks owned by sequences that
        finish mid-pipeline receive up to one chunk of speculative garbage
        writes, so their allocations are parked in ``_zombie_allocs`` and
        freed only once the in-flight chunk has been fetched."""
        tl = self._timeline
        t_step = time.perf_counter() if self._straggler is not None else 0.0
        self._prepare_lanes()
        decode = self._decode_dispatch()
        if decode is None:
            return
        self._clock.steps[1] += 1
        prev, n_active = decode
        if prev is not None:
            self._process_chunk(prev, defer_free=True)
        k = self.config.decode_steps
        if tl is not None and tl.should_sample():
            self._note_dispatch("decode", n_active, n_active * k)
        if self._straggler is not None:
            self._straggler_tick("decode", t_step, n_active * k)

    def _decode_dispatch(self) -> Optional[Tuple[Optional[_Inflight], int]]:
        """Dispatch the decode program over the live lanes (after
        `_prepare_lanes`) and return (the dispatch it displaced, still to be
        processed; live lanes). None when nothing was dispatched: no lane
        decodes, or none needs more than what is in flight."""
        clock = self._clock
        with clock(P_DECODE_BUILD):
            built = self._decode_build()
        if built is None:
            return None
        fn, args, want_lp, want_pen, live, filled, n_active = built
        with clock(P_DECODE_DISPATCH if clock.compile_key is None else P_COMPILE):
            *done, counts_out = self._take_state(fn(*args))
            clock.dispatched(1)
        drafts = done.pop() if self._device_drafts else None
        sums = done.pop() if self._own_programs else None
        out, *lp_out, toks2, pos2 = done
        lps, tids, tlps = lp_out if want_lp else (None, None, None)
        if want_pen:
            self._counts = counts_out
        else:
            self._dummy_counts = counts_out
            self._release_counts()
        prev, self._inflight = (
            self._inflight,
            _Inflight(out, lps, tids, tlps, toks2, pos2, live,
                      self._take_sealing(filled), sums, drafts),
        )
        # start the host copies now: by the time this chunk is processed (one
        # pipelined dispatch later) the fetch has ridden the previous chunk's
        # compute window and the blocking get is ~free (vs ~100 ms cold)
        for arr in (out, lps, tids, tlps):
            if arr is not None:
                arr.copy_to_host_async()
        return prev, n_active

    def _decode_build(self):
        """The host's half of `_decode_dispatch`: what is in flight drained
        where the live set changed, then the program and its arguments."""
        cfg = self.config
        S, k = cfg.max_slots, cfg.decode_steps
        if self._inflight is not None and self._carry_is_stale():
            # the carry no longer matches; fall back to host-built inputs
            self._drain_inflight()
        live = self._live_lanes()
        n_active = sum(1 for s in live if s is not None)
        if not n_active:
            # nothing left to carry forward: what is in flight ends here
            self._drain_inflight()
            return None

        # Don't dispatch a chunk nothing needs: if every active lane provably
        # reaches a length stop within the already-in-flight chunk, a
        # speculative dispatch would compute decode_steps of garbage that the
        # NEXT admission wave then queues behind (at large decode_steps that
        # stalls a whole wave's TTFT by a full chunk).
        def lane_needs_more(seq: "_Seq") -> bool:
            ahead = k if (
                self._inflight is not None
                and seq.slot is not None
                and self._inflight.lanes[seq.slot] is seq
            ) else 0
            if seq.emitted + ahead >= seq.max_tokens:
                return False
            if seq.total_len + ahead >= self.config.max_model_len:
                return False
            return True

        if not any(lane_needs_more(s) for s in live if s is not None):
            self._drain_inflight()
            return None

        for i in range(S):
            seq = live[i]
            self._tables[i, :] = 0
            if seq is None:
                # empty lane — or a prefilling lane, which is a row of the
                # chunk program and not of this one
                self._positions[i] = -1
                self._last_tokens[i] = 0
                self._temp[i] = 0.0
                self._topk[i] = 0
                self._topp[i] = 1.0
                self._seeds[i] = 0
                self._freqp[i] = 0.0
                self._presp[i] = 0.0
                continue
            self._positions[i] = seq.total_len - 1
            self._last_tokens[i] = seq.generated[-1] if seq.generated else seq.prompt[-1]
            self._tables[i, : len(seq.alloc.block_ids)] = seq.alloc.block_ids
            self._temp[i] = seq.temperature
            self._topk[i] = seq.top_k
            self._topp[i] = seq.top_p
            self._seeds[i] = seq.seed & 0x7FFFFFFF
            self._freqp[i] = seq.freq_pen
            self._presp[i] = seq.pres_pen

        use_carry = self._inflight is not None
        # the blocks this dispatch fills (they seal when it is processed). A
        # lane riding the carry is k positions further than the host has
        # emitted: what is in flight comes first.
        ahead = k if use_carry else 0
        filled: List[int] = []
        for seq in live:
            if seq is not None:
                filled += self._blocks_filled(
                    seq.alloc, seq.total_len - 1 + ahead, k
                )
        if self._decode_dense and self._pp == 1:
            bs, MB = cfg.kv_block_size, cfg.max_blocks_per_seq
            full = S * history_tiles_full(bs, MB)
            self.decode_history_tiles_full += full
            # `models/llama.py`'s programs on a mesh gather every table's full
            # width; else what the module says (the live pairs, or every table
            # whole), a shard of a module that serves on a mesh as one device
            self.decode_history_tiles_read += full if (
                self.mesh is not None and not self._own_programs
            ) else int(
                self.model.decode_history_tiles(
                    np.where(self._positions < 0, -1, self._positions + ahead),
                    bs, MB,
                )
            )
        if use_carry:
            toks_in, pos_in = self._inflight.tokens, self._inflight.positions
        else:
            toks_in = self._put(self._last_tokens)
            pos_in = self._put(self._positions)

        self._step_counter += 1
        want_lp = any(s is not None and s.logprobs is not None for s in live)
        want_pen = any(s is not None and s.penalized for s in live)
        want_sample = any(s is not None and s.temperature > 0.0 for s in live)
        if want_pen:
            self._sync_counts(list(self._slots))
        counts_in = self._counts if want_pen else self._dummy_counts
        ipack_np = np.stack([self._seeds, self._topk])
        fpack_np = np.stack([self._temp, self._topp, self._freqp, self._presp])
        if self._dispatch_hook is not None:
            self._dispatch_hook(
                "decode",
                dict(lp=want_lp, pen=want_pen, sample=want_sample,
                     use_carry=use_carry, step=self._step_counter),
                dict(tokens=self._last_tokens, positions=self._positions,
                     tables=self._tables, ipack=ipack_np, fpack=fpack_np),
            )
        args = self._state_args(self.params_decode) + (
            counts_in, toks_in, pos_in,
            self._m_tables.get(self._tables),
            self._put(np.int32(self._step_counter)),
            self._m_ipack.get(ipack_np),
            self._m_fpack.get(fpack_np),
        ) + self._wd_args()
        self._slow_fault()
        fn = self._decode(want_lp, want_pen, want_sample)
        return fn, args, want_lp, want_pen, live, filled, n_active

    def _emit_token_run(
        self,
        seq: "_Seq",
        cand: List[int],
        lp_rows,  # None, or (lps_row [k], tids_row [k, n_top], tlps_row)
        *,
        defer_free: bool = False,
    ) -> int:
        """Emit one multi-token run for a lane — the shared tail of the
        pipelined chunk and the speculative verify dispatch. Cuts the
        candidate run at max_tokens / max_model_len / first EOS, registers
        fed-token KV, assembles logprobs, emits ONE item (per-token emission
        costs a dict build + a call_soon_threadsafe wakeup each — at 32
        lanes × 64-step chunks that Python overhead rivals the decode step's
        device time), and finishes the lane on a terminal cut. Returns the
        number of tokens actually emitted."""
        if self._watchdog and any(t < 0 for t in cand):
            # output watchdog sentinel: this dispatch produced non-finite /
            # exploding logits for the lane. NOTHING from the run is
            # emitted or sealed — the whole run is suspect — and the lane
            # ends typed + in-band (resume directive) so the client
            # re-admits on a sibling (docs/resilience.md §Silent corruption)
            self._watchdog_trip(seq, defer_free=defer_free)
            return 0
        cfg = self.config
        n_take = min(
            len(cand),
            seq.max_tokens - seq.emitted,
            cfg.max_model_len - seq.total_len,
        )
        finish: Optional[FinishReason] = None
        if n_take < len(cand):
            finish = FinishReason.LENGTH
        toks = cand[:n_take]
        if seq.eos_ids and not seq.ignore_eos:
            for j, t in enumerate(toks):
                if t in seq.eos_ids:
                    toks = toks[: j + 1]
                    finish = FinishReason.EOS
                    break
        if not toks:
            if finish is not None:
                self._finish(seq, finish, defer_free=defer_free)
            return 0
        if finish is None and seq.emitted + len(toks) >= seq.max_tokens:
            finish = FinishReason.LENGTH
        elif finish is None and seq.total_len + len(toks) >= cfg.max_model_len:
            finish = FinishReason.LENGTH
        # fed tokens whose KV is valid AND part of the sequence: the carried
        # last token plus every emitted token bar the final one (in the
        # verify dispatch, matched drafts ARE the emitted prefix)
        fed0 = seq.generated[-1] if seq.generated else seq.prompt[-1]
        self.allocator.note_tokens_computed(seq.alloc, [fed0] + toks[:-1])

        log_probs = top_logprobs = None
        if lp_rows is not None and seq.logprobs is not None:
            lps_row, tids_row, tlps_row = lp_rows
            n = len(toks)
            log_probs = [float(x) for x in lps_row[:n]]
            if seq.logprobs > 0:
                kk = min(seq.logprobs, tids_row.shape[1])
                top_logprobs = [
                    {int(tids_row[j, p]): float(tlps_row[j, p])
                     for p in range(kk)}
                    for j in range(n)
                ]
        seq.generated.extend(toks)
        seq.out_tokens.extend(toks)
        if seq.drafter is not None:
            seq.drafter.extend(toks)
        seq.emitted += len(toks)
        self.total_generated_tokens += len(toks)
        if self._fair is not None and seq.tenant:
            self._fair.charge(seq.tenant, len(toks), seq.weight)
        seq.emit(Annotated.from_data(
            LLMEngineOutput(
                token_ids=toks, log_probs=log_probs, top_logprobs=top_logprobs
            ).to_dict(),
            id=seq.ctx.id,
        ))
        if finish is not None:
            self._finish(seq, finish, defer_free=defer_free)
        return len(toks)

    def _process_chunk(self, chunk: _Inflight, defer_free: bool) -> None:
        if self._perf is not None:
            # gap between consecutive processed chunks ≈ chunk wall time in
            # pipelined decode; tokens counted below feed the tps EMA
            tokens_before = self.total_generated_tokens
            self._perf.note_slots(
                sum(1 for s in chunk.lanes if s is not None),
                self.config.max_slots,
            )
        clock = self._clock
        # the read of a pipeline being emptied (`_drain_inflight`) is the wait
        # the host chose: `engine.drain`; what it emits is an emit as any other
        with clock(P_DECODE_FETCH if defer_free else P_DRAIN):
            # dynlint: allow-host-sync(leader sync: pipelined fetch — the copy
            # rode the NEXT chunk's compute window, ~free by the time we get)
            out, lps, tids, tlps, sums, drafts = jax.device_get(
                (chunk.out, chunk.lps, chunk.top_ids, chunk.top_lps, chunk.sums,
                 chunk.drafts)
            )
            clock.fetched(1)
        out = np.asarray(out)  # [S, k_steps]
        with clock(P_DECODE_EMIT):
            if sums is not None:
                self._add_model_counters(sums)
            self._sealing = chunk.sealing
            for i, seq in enumerate(chunk.lanes):
                if seq is None or seq.slot != i:
                    # not live in this dispatch (empty, or prefilling then: its
                    # row is garbage, not tokens), or finished in an earlier chunk
                    continue
                start = seq.total_len
                self._emit_token_run(
                    seq,
                    [int(t) for t in out[i]],
                    (lps[i], tids[i], tlps[i]) if lps is not None else None,
                    defer_free=defer_free,
                )
                if drafts is not None and seq.drafter is not None:
                    # the guess follows the dispatch's LAST token: of use
                    # only to a lane that emitted them all (`DeviceDrafter`)
                    seq.drafter.offer(int(drafts[i]), start + out.shape[1])
            self._sealing = None
        if self._perf is not None:
            self._perf.note_decode(
                self.total_generated_tokens - tokens_before,
                self.config.decode_steps,
            )

    def _verify_step(self) -> None:
        """One speculative-verify dispatch (self-draft, engine_jax/drafter.py).

        Probes every decode lane's n-gram drafter, feeds ``[last_token,
        draft_0..draft_{k-1}]`` through the jit verify variant (one weight
        stream for all K1 positions), and accepts the longest drafted prefix
        matching the in-jit sampled targets plus the first non-matching
        target as the bonus token. Greedy output is bitwise identical to the
        sequential decode path; sampled output follows the exact
        autoregressive distribution (speculative_targets docstring).

        Not pipelined: the next dispatch's fed tokens depend on this one's
        acceptance, so the chunk is fetched synchronously — the amortized
        weight stream is what pays for the lost overlap. When no lane
        drafts (cold drafters, dormant after sustained rejection), control
        falls through to the plain pipelined decode step, so adversarial
        workloads keep the non-speculative fast path."""
        cfg = self.config
        S = cfg.max_slots
        tl, clock = self._timeline, self._clock
        t_step = time.perf_counter() if self._straggler is not None else 0.0
        # host needs every lane's true last token and the drafters need the
        # emitted suffix up to date before proposing
        self._drain_inflight()
        for seq in [
            s for s in self._slots
            if s is not None and s.ctx.context.is_stopped
        ]:
            self._finish(seq, FinishReason.CANCELLED)
        if not any(self._slots):
            return

        drafts: List[Optional[List[int]]] = [None] * S
        n_drafted = 0
        for i, seq in enumerate(self._slots):
            if seq is None or seq.drafter is None:
                continue
            # cap: fed positions must stay under max_model_len, and drafts
            # past the request's remaining token budget are dead weight
            cap = min(
                self._spec_k,
                cfg.max_model_len - seq.total_len,
                seq.max_tokens - seq.emitted,
            )
            if cap <= 0:
                continue
            d = seq.drafter.draft()
            if d:
                drafts[i] = d[:cap]
                n_drafted += len(drafts[i])
        if n_drafted == 0:
            self._decode_step()
            return

        # capacity for the drafted positions (non-pipelined: preemption here
        # has no zombie-chunk complication)
        for i, seq in enumerate(self._slots):
            if seq is None:
                continue
            need = min(seq.total_len + len(drafts[i] or []), cfg.max_model_len)
            if not self.allocator.grow(seq.alloc, need):
                drafts[i] = None
                self._preempt(seq)
        if not any(self._slots):
            return
        if not any(
            drafts[i] for i in range(S) if self._slots[i] is not None
        ):
            self._decode_step()
            return

        k1 = self._spec_k + 1
        tokens = np.zeros((S, k1), np.int32)
        positions = np.full((S, k1), -1, np.int32)
        for i in range(S):
            seq = self._slots[i]
            self._tables[i, :] = 0
            self._temp[i] = 0.0
            self._topk[i] = 0
            self._topp[i] = 1.0
            self._seeds[i] = 0
            self._freqp[i] = 0.0
            self._presp[i] = 0.0
            if seq is None:
                continue
            fed = [seq.generated[-1] if seq.generated else seq.prompt[-1]]
            fed += drafts[i] or []
            n = len(fed)
            tokens[i, :n] = fed
            positions[i, :n] = np.arange(seq.total_len - 1, seq.total_len - 1 + n)
            self._tables[i, : len(seq.alloc.block_ids)] = seq.alloc.block_ids
            self._temp[i] = seq.temperature
            self._topk[i] = seq.top_k
            self._topp[i] = seq.top_p
            self._seeds[i] = seq.seed & 0x7FFFFFFF
            self._freqp[i] = seq.freq_pen
            self._presp[i] = seq.pres_pen

        self._step_counter += 1
        lanes = list(self._slots)
        want_lp = any(s is not None and s.logprobs is not None for s in lanes)
        want_pen = any(s is not None and s.penalized for s in lanes)
        want_sample = any(s is not None and s.temperature > 0.0 for s in lanes)
        if want_pen:
            self._sync_counts(lanes)
        counts_in = self._counts if want_pen else self._dummy_counts
        ipack_np = np.stack([self._seeds, self._topk])
        fpack_np = np.stack([self._temp, self._topp, self._freqp, self._presp])
        args = (
            self.params_decode, self.cache, counts_in, self._put(tokens),
            self._put(positions), self._m_tables.get(self._tables),
            self._put(np.int32(self._step_counter)),
            self._m_ipack.get(ipack_np), self._m_fpack.get(fpack_np),
        ) + self._wd_args()
        self._slow_fault()
        fn = self._verify(want_lp, want_pen, want_sample)
        # a verify dispatch is a decode dispatch to the clock: its phases
        with clock(P_DECODE_DISPATCH if clock.compile_key is None else P_COMPILE):
            *fetch, self.cache, counts_out = fn(*args)
            clock.dispatched(1)
        with clock(P_DECODE_FETCH):
            # dynlint: allow-host-sync(leader sync: one fetch per verify
            # dispatch — acceptance decides the next dispatch's inputs, so
            # this path is deliberately not pipelined)
            tgt_np, *lp_nps = jax.device_get(fetch)
            clock.fetched(1)
        # a module's own program: its sums and, where it drafts, its guesses
        next_np = lp_nps.pop() if self._device_drafts else None
        if self._own_programs:
            self._add_model_counters(lp_nps.pop())
        tgt_np = np.asarray(tgt_np)
        lp_np, tids_np, tlps_np = lp_nps if want_lp else (None, None, None)
        clock.steps[1] += 1
        if want_pen:
            self._counts = counts_out
        else:
            self._dummy_counts = counts_out
            self._release_counts()

        if self._perf is not None:
            tokens_before = self.total_generated_tokens
            self._perf.note_slots(
                sum(1 for s in self._slots if s is not None), S
            )
        drafted_total = accepted_total = 0
        fix_pairs: List[Tuple[int, int]] = []
        for i in range(S):
            seq = self._slots[i]
            if seq is None:
                continue
            d = drafts[i] or []
            row = tgt_np[i]
            a = 0
            while a < len(d) and int(row[a]) == d[a]:
                a += 1
            if d:
                seq.drafter.note_result(len(d), a)
                seq.spec_drafted += len(d)
                seq.spec_accepted += a
                self.spec_drafted_total += len(d)
                self.spec_accepted_total += a
                drafted_total += len(d)
                accepted_total += a
            penalized = seq.penalized
            # emitted run: matched drafts + the bonus target, then the same
            # cut rules as _process_chunk (shared _emit_token_run tail)
            start = seq.total_len
            n_emitted = self._emit_token_run(
                seq,
                [int(t) for t in row[: a + 1]],
                (lp_np[i], tids_np[i], tlps_np[i])
                if lp_np is not None else None,
            )
            if next_np is not None and seq.drafter is not None:
                # the prediction module's choice behind the last accepted
                # token (position a's target): the next dispatch's draft
                seq.drafter.offer(int(next_np[i, a]), start + a + 1)
            if want_pen and penalized:
                # the scan added EVERY active position's target into this
                # lane's count row (sequential exactness up to the first
                # mismatch costs pollution past it); subtract the targets
                # that were NOT emitted — rejected positions plus any cut
                # by max_tokens / max_model_len / EOS
                for j in range(n_emitted, 1 + len(d)):
                    fix_pairs.append((i, int(row[j])))
        if fix_pairs and self._counts is not None:
            pb = 1
            while pb < len(fix_pairs):
                pb *= 2
            rows = np.full((pb,), S, np.int32)
            toks_np = np.zeros((pb,), np.int32)
            for j, (r, t) in enumerate(fix_pairs):
                rows[j] = r
                toks_np[j] = t
            self._counts = self._counts_fix_fn(pb)(
                self._counts, self._put(rows), self._put(toks_np)
            )
        if self._perf is not None:
            self._perf.note_decode(
                self.total_generated_tokens - tokens_before, 1
            )
            self._perf.note_spec(drafted_total, accepted_total)
        if tl is not None and tl.should_sample():
            n_live = sum(1 for s in self._slots if s is not None)
            self._note_dispatch("verify", n_live, accepted_total + n_live)
        if self._straggler is not None:
            self._straggler_tick(
                "verify", t_step,
                accepted_total + sum(
                    1 for s in self._slots if s is not None
                ),
            )

    def _drain_inflight(self) -> None:
        """Fetch + process any in-flight chunk, then release zombie blocks
        (no further speculative writes can touch them)."""
        if self._inflight is not None:
            chunk, self._inflight = self._inflight, None
            self._process_chunk(chunk, defer_free=False)
        for alloc in self._zombie_allocs:
            self.allocator.free_sequence(alloc)
        self._zombie_allocs.clear()

    def _emit_token(
        self, seq: _Seq, tok: int, defer_free: bool = False, lpinfo=None
    ) -> None:
        seq.generated.append(tok)
        seq.out_tokens.append(tok)
        if seq.drafter is not None:
            seq.drafter.extend((tok,))
        seq.emitted += 1
        self.total_generated_tokens += 1
        if self._fair is not None and seq.tenant:
            self._fair.charge(seq.tenant, 1, seq.weight)
        finish: Optional[FinishReason] = None
        if tok in seq.eos_ids and not seq.ignore_eos:
            finish = FinishReason.EOS
        elif seq.emitted >= seq.max_tokens:
            finish = FinishReason.LENGTH
        elif seq.total_len >= self.config.max_model_len:
            finish = FinishReason.LENGTH

        log_probs = top_logprobs = None
        if seq.logprobs is not None and lpinfo is not None:
            chosen_lp, top_ids, top_lps = lpinfo
            log_probs = [chosen_lp]
            if seq.logprobs > 0:
                k = min(seq.logprobs, len(top_ids))
                top_logprobs = [
                    {int(top_ids[p]): float(top_lps[p]) for p in range(k)}
                ]
        seq.emit(Annotated.from_data(
            LLMEngineOutput(
                token_ids=[tok], log_probs=log_probs, top_logprobs=top_logprobs
            ).to_dict(),
            id=seq.ctx.id,
        ))
        if finish is not None:
            self._finish(seq, finish, defer_free=defer_free)

    def _record_phase_spans(self, seq: _Seq, reason: FinishReason) -> None:
        """Retroactive phase spans from the timestamps the hot path already
        stamps (engine thread, once per request — dispatch loops stay
        allocation-free). queue_wait = enqueue → slot admission; prefill =
        admission → first token (remote prefills collapse queue_wait into
        prefill: the wait WAS the remote compute); decode = first token →
        finish, with the token count."""
        now = time.perf_counter()
        parent = seq.ctx.context.trace
        status = tracing.STATUS_OK
        if reason == FinishReason.CANCELLED:
            status = "cancelled"
        elif reason == FinishReason.ERROR:
            status = "error"
        attrs = {
            "request_id": seq.ctx.id,
            "prompt_tokens": len(seq.prompt),
            "output_tokens": seq.emitted,
            "remote_prefill": seq.remote,
            "finish_reason": str(getattr(reason, "value", reason)),
        }
        if seq.tenant:
            # per-tenant phase-latency attribution (docs/qos.md): every
            # phase span below parents here, so a tenant filter over the
            # flight recorder yields that tenant's queue/prefill/decode
            # breakdown
            attrs["tenant"] = seq.tenant
        if seq.resumed:
            # resumed re-admission: its "prefill" is a recovery recompute of
            # another worker's dead stream, not an admission wait — SLO
            # consumers exclude it from TTFT (docs/resilience.md)
            attrs["resumed"] = True
        if seq.migrated:
            # migrated re-home: the staged KV made the re-admission
            # recompute-free (docs/resilience.md §Live migration)
            attrs["migrated"] = True
        req_span = tracing.record_span(
            "engine.request", seq.enqueue_t, now, parent=parent,
            attributes=attrs,
            status=status,
        )
        parent = req_span or parent
        first = seq.first_token_t
        prefill_start = seq.enqueue_t
        if (
            seq.admit_t is not None
            and (first is None or seq.admit_t <= first)
        ):
            prefill_start = seq.admit_t
        tracing.record_span(
            "engine.queue_wait", seq.enqueue_t, prefill_start,
            parent=parent, phase="queue_wait",
        )
        if first is not None:
            tracing.record_span(
                "engine.prefill", prefill_start, first, parent=parent,
                phase="prefill",
                attributes={
                    **({"remote": True} if seq.remote else {}),
                    # a slot model passed these cached tokens over: their
                    # pages would have come without the slot's state
                    **({"prefix_hit_declined_for_state": seq.prefix_declined}
                       if seq.prefix_declined else {}),
                } or None,
            )
            decode_attrs: Dict[str, Any] = {"tokens": seq.emitted}
            if seq.spec_drafted:
                # per-request speculation outcome on the decode span, plus a
                # dimensionless acceptance-rate observation (0..1) on the
                # spec_accept phase histogram — p50/p95 of per-request
                # acceptance through the same pipeline as the latencies
                decode_attrs["spec_drafted"] = seq.spec_drafted
                decode_attrs["spec_accepted"] = seq.spec_accepted
                tracing.observe_phase(
                    "spec_accept", seq.spec_accepted / seq.spec_drafted
                )
            tracing.record_span(
                "engine.decode", first, now, parent=parent, phase="decode",
                attributes=decode_attrs,
            )

    def _finish(self, seq: _Seq, reason: FinishReason, defer_free: bool = False) -> None:
        if tracing.enabled():
            self._record_phase_spans(seq, reason)
        if seq.slot is not None:
            self._slots[seq.slot] = None
            seq.slot = None
        if not any(self._slots):
            # the last stream ends: its caller finds every crc registered
            self._seal_await()
        if seq.alloc is not None:
            if seq.ctx.id in self._hold_ids:
                # prefill-worker mode: park the pages for extraction; the
                # caller frees via take_held_pages/release_held. Safe without
                # zombie-parking: held requests are prompt-only (finish in
                # the chunk step), so no speculative decode writes them.
                self._held_allocs[seq.ctx.id] = seq.alloc
                seq.alloc = None
            elif defer_free:
                # the in-flight speculative chunk may still write into these
                # blocks; park them until it has been fetched
                self._zombie_allocs.append(seq.alloc)
            else:
                self.allocator.free_sequence(seq.alloc)
            seq.alloc = None
        seq.emit(Annotated.from_data(LLMEngineOutput.final(reason).to_dict(), id=seq.ctx.id))
        seq.emit(_FINISHED)

    def _watchdog_trip(self, seq: _Seq, defer_free: bool = False) -> None:
        """Output watchdog (docs/resilience.md §Silent corruption): the
        lane's dispatch produced non-finite or exploding logits. The lane
        dies HERE, typed and in-band — the PR10 contract (never raise past
        delivered tokens) means the stream ends with an explicit resume
        directive: a journaled client re-admits on a sibling and the
        caller sees an unbroken, byte-correct stream; a journal-less
        client gets an explicit in-band error, never silent garbage.
        Nothing from the tripped dispatch is emitted or sealed (the KV it
        wrote is suspect too); the lane's UNSEALED tail blocks free with
        the allocation, its pre-trip sealed blocks were computed by
        healthy dispatches and stay cached. The trip counts against this
        worker's quarantine window. Engine thread only."""
        self.watchdog_trips += 1
        integrity_mod.note_trip("watchdog", where="engine")
        logger.error(
            "output watchdog tripped for request %s: non-finite or "
            "exploding logits — ending the stream with a resume directive",
            seq.ctx.id,
        )
        if tracing.enabled():
            self._record_phase_spans(seq, FinishReason.ERROR)
        if seq.slot is not None:
            self._slots[seq.slot] = None
            seq.slot = None
        if seq.alloc is not None:
            if defer_free:
                # the in-flight speculative chunk may still write into
                # these blocks; park them until it has been fetched
                self._zombie_allocs.append(seq.alloc)
            else:
                self.allocator.free_sequence(seq.alloc)
            seq.alloc = None
        seq.emit(Annotated.from_data(
            {"migrating": {
                "resume": True,
                "error": "output watchdog: non-finite or exploding logits",
            }},
            id=seq.ctx.id,
        ))
        seq.emit(_FINISHED)

    def _preempt(self, seq: _Seq) -> None:
        """Out of KV blocks mid-decode: recompute-preempt — free pages, requeue
        with prompt := prompt + generated, prefix cache softens the recompute.

        ``generated`` is cleared so positions/total_len stay consistent after
        re-admission (it had been double-counted before, writing KV at wrong
        slots with wrong RoPE); ``seq.emitted`` keeps the caller-visible token
        count for max_tokens."""
        logger.warning("preempting request %s (out of KV blocks)", seq.ctx.id)
        self.preemptions += 1
        if seq.slot is not None:
            self._slots[seq.slot] = None
            seq.slot = None
        self.allocator.free_sequence(seq.alloc)
        seq.prompt = seq.prompt + seq.generated
        seq.generated = []
        seq.alloc = None
        seq.prefill_pos = None  # re-set from the fresh allocation on re-admit
        with self._cond:
            self._pending.append(seq)

    # -- disaggregated prefill ------------------------------------------------

    def set_remote_prefill_policy(self, policy) -> None:
        """policy must provide should_remote(uncached_len)->bool and
        submit(request_id, token_ids, block_ids, cached_tokens, sampling)
        (called from the engine thread; submit must be thread-safe)."""
        self._refuse_for_state("disaggregated prefill")
        self._remote_policy = policy

    def extract_blocks(
        self, block_ids: List[int], as_device: bool = False
    ) -> kv_pages.Pages:
        """Copy KV pages out of the pool: a page set of kv/pages.py, every
        member of the pool ``[L, n, bs, ...]`` (scale tables travel WITH
        their pages through every transfer tier). Host numpy, or device
        arrays with ``as_device`` (same-host transfers keep pages on-device
        and let XLA reshard at the destination's inject boundary).
        MUST run on the engine thread (e.g. via post())."""
        self._refuse_for_state("a transfer of pages out of the pool")
        taken = kv_pages.take(self.cache, block_ids)
        if as_device:
            return taken
        # dynlint: allow-host-sync(page extraction for KV transfer; off the
        # decode loop, every member's copy started before the first wait)
        return kv_pages.to_host(taken)

    def block_hashes_of(self, block_ids: List[int]) -> List[int]:
        """The allocator-registered content hash per physical page (-1 for a
        page with no registered hash — free, partial, or reused). Lets a
        remote reader verify pages still hold the content it expects; MUST
        run on the engine thread."""
        return [self.allocator.hash_of_block(bid) for bid in block_ids]

    def block_crcs_of(self, block_ids: List[int]) -> List[int]:
        """Seal-time content checksums per physical page (-1 when unsealed
        or sealed before the integrity plane was on). Transfer tiers ship
        these next to the pages; a -1 entry means "sender can't vouch" and
        receivers fall back to extract-time (wire-only) checksums. MUST run
        on the engine thread."""
        return [self.allocator.crc_of_block(bid) for bid in block_ids]

    def _seal_crcs(self, block_ids: List[int], generation: int) -> None:
        """The allocator's seal-time checksum callback: hand the freshly
        sealed blocks' pages to the checksum worker, which waits for their
        host copy, hashes each block and publishes its crc under
        ``generation`` (`_seal_land` registers it). This is the integrity
        plane's steady-state cost, knob-gated by DYN_TPU_KV_INTEGRITY: one
        small device→host copy per sealed block, and the hand-over on this
        thread. Where the dispatch being processed had these blocks' pages
        taken as it was dispatched (`_take_sealing`), those are the pages;
        else they are taken here, so the bytes are the seal's either way.
        With more than `_seal_backlog_bytes` handed over and not hashed, the
        engine thread waits for the oldest. MUST run on the engine thread
        (note_tokens_computed call sites)."""
        worker = self._crc_worker
        with self._clock(P_SEAL_CRC):
            ahead = self._sealing
            if ahead is None or not all(b in ahead.where for b in block_ids):
                ahead = self._seal_read(block_ids)
            self.seal_crc_blocks += len(block_ids)
            worker.submit(
                ahead, block_ids, generation, len(block_ids) * self._block_bytes
            )
            if worker.pending_bytes > self._seal_backlog_bytes:
                self.seal_crc_backlog_waits += 1
                worker.wait(self._seal_backlog_bytes)

    def _seal_land(self) -> None:
        """Register the crcs the worker has finished with the allocator,
        which drops one whose block was resealed or unregistered since.
        Raises what the worker raised. Engine thread: the top of a host step,
        and wherever a crc is read."""
        for bid, generation, crc in self._crc_worker.take_done():
            self.allocator.crc_landed(bid, generation, crc)

    def _seal_await(self, block_id: Optional[int] = None) -> None:
        """Wait on the engine thread for the crcs that are pending and
        register them: the allocator's callback where a reader found
        ``block_id``'s pending (counted), and with None what makes the
        registry whole where no slot is live."""
        worker = self._crc_worker
        if not self._seal_unsettled():
            return
        with self._clock(P_SEAL_CRC):
            self._seal_land()
            if block_id is not None:
                if not self.allocator.crc_pending(block_id):
                    return  # it was finished, only not yet registered
                self.seal_crc_reader_waits += 1
            t0 = time.perf_counter()
            worker.wait(0)
            self._seal_land()
            if block_id is not None:
                self.seal_crc_reader_wait_us += (time.perf_counter() - t0) * 1e6

    def _seal_unsettled(self) -> bool:
        """Is a crc with the worker, or finished and not yet registered?"""
        worker = self._crc_worker
        return worker is not None and bool(worker.pending_blocks or worker.has_done)

    def _seal_read(self, block_ids: List[int]) -> _SealPages:
        """Enqueue the read of ``block_ids`` off the pool, behind whatever
        has been dispatched, and start its copy to the host."""
        pages = kv_pages.take(self.cache, block_ids)
        for a in pages.values():
            a.copy_to_host_async()
        return _SealPages(pages, {b: j for j, b in enumerate(block_ids)})

    def _take_sealing(self, filled: List[int]) -> Optional[_SealPages]:
        """Enqueue, behind the program just dispatched, the read of the blocks
        it fills to their end (``filled``, by `_blocks_filled`), and start
        their copy to the host. The list is padded with its last id to one
        of `_sealing_sizes`."""
        n = len(filled)
        if not self._seal_checksums or not 0 < n <= max(self._sealing_sizes, default=0):
            return None
        size = next(b for b in self._sealing_sizes if b >= n)
        with self._clock(P_SEAL_READ):
            return self._seal_read(filled + filled[-1:] * (size - n))

    def _blocks_filled(self, alloc: SequenceAllocation, start: int, n: int) -> List[int]:
        """The blocks of ``alloc`` that positions ``[start, start + n)`` fill
        to their end: those seal once the positions are noted as computed."""
        bs = self.config.kv_block_size
        return alloc.block_ids[start // bs : (start + n) // bs]

    def seed_external_prefix(
        self, token_ids: List[int], pages: kv_pages.Pages
    ) -> int:
        """Register externally-computed prefix KV (pages read from another
        worker) into this engine's prefix cache: allocator registration +
        page injection, atomically on the engine thread. ``pages`` covers
        ALL full blocks of ``token_ids``; already-cached blocks are skipped.
        Returns the number of blocks seeded.
        MUST run on the engine thread (via post())."""
        # check BEFORE touching the allocator: a mismatch must not leave
        # seeded-but-never-injected hashes in the prefix cache
        self._refuse_for_state("a prefix seeded from another worker")
        kv_pages.check(self.cache, pages)
        pairs = self.allocator.seed_cached(token_ids)
        if not pairs:
            return 0
        self.inject_blocks(
            [bid for _, bid in pairs],
            kv_pages.select(pages, [i for i, _ in pairs]),
        )
        return len(pairs)

    # -- held allocations (prefill-worker page extraction) --------------------

    def hold_pages(self, request_id: str) -> None:
        """Mark a request's pages to be parked (not freed) when it finishes,
        so a caller can extract them afterwards. Thread-safe; call before
        submitting the request. Pair with :meth:`release_held`."""
        self._hold_ids.add(request_id)

    def take_held_pages(
        self, request_id: str, first_block: int, n_blocks: int,
        as_device: bool = False,
    ):
        """Extract pages [first_block, n_blocks) of a finished held request,
        then release its allocation. MUST run on the engine thread."""
        self._hold_ids.discard(request_id)
        alloc = self._held_allocs.pop(request_id, None)
        if alloc is None:
            raise KeyError(f"no held allocation for request {request_id}")
        try:
            ids = alloc.block_ids[first_block:n_blocks]
            return self.extract_blocks(ids, as_device=as_device)
        finally:
            self.allocator.free_sequence(alloc)

    def release_held(self, request_id: str) -> None:
        """Free a held allocation without extracting (error paths).
        MUST run on the engine thread."""
        self._hold_ids.discard(request_id)
        alloc = self._held_allocs.pop(request_id, None)
        if alloc is not None:
            self.allocator.free_sequence(alloc)

    # -- live in-flight migration (disagg/migration.py) -----------------------
    #
    # Source side: export_migratable freezes mid-decode sequences; the drain
    # coordinator extracts their pages, ships a `migrate` frame, and ends
    # each stream with an in-band marker (finish_migrated / abort_migration).
    # Target side: stage_migration adopts the pages into a pre-built
    # allocation whose cached_tokens covers every already-computed position
    # (0..N-2 of the N-token prompt+emitted history — position N-1 was never
    # computed anywhere: the source sampled its token but hadn't fed it yet).
    # The re-homed client's attach then rides the ORDINARY admission path for
    # a pre-held allocation: prefill_pos = N-1, one fresh position computed,
    # zero positions recomputed, greedy continuation bitwise identical.

    def export_migratable(self) -> List[dict]:
        """Freeze every migratable sequence (mid-decode, ≥1 generated token,
        not remote-awaiting/cancelled) out of its slot and return one
        checkpoint per stream. Frozen sequences stop decoding but keep
        their allocation until finish/abort/unfreeze. MUST run on the
        engine thread (via post())."""
        self._refuse_for_state("export_migratable")
        self._drain_inflight()  # commit speculative writes; host state final
        out: List[dict] = []
        bs = self.config.kv_block_size
        for i, seq in enumerate(self._slots):
            if (
                seq is None or seq.prefill_pos is not None
                or not seq.generated or seq.ctx.context.is_stopped
            ):
                continue
            self._slots[i] = None
            seq.slot = None
            self._migrating_out[seq.ctx.id] = seq
            toks = seq.prompt + seq.generated
            n_hist = len(toks) - 1
            out.append({
                "request_id": seq.ctx.id,
                "mid": uuid.uuid4().hex[:16],
                "token_ids": toks,
                # caller-visible output across ALL legs of this stream
                # (out_tokens carries resume/migrate-seeded history plus
                # everything emitted here) — the client validates its
                # journal against this; seq.emitted would under-count a
                # stream that already migrated once
                "emitted": len(seq.out_tokens),
                "tenant": seq.tenant,
                "level": seq.level,
                "n_blocks": (n_hist + bs - 1) // bs,
            })
        return out

    def extract_for_migration(self, request_id: str):
        """Copy a frozen sequence's computed-history pages out of the pool:
        blocks covering positions 0..N-2 (the last sampled token was never
        fed, so its position has no KV anywhere). Returns ``(pages,
        crcs)`` — ``crcs`` is the per-block content checksum list the
        migrate frame ships (seal-time registry values where the block is
        sealed, extract-time values for the partial tail; None with the
        integrity plane off). MUST run on the engine thread."""
        seq = self._migrating_out[request_id]  # KeyError → coordinator aborts
        n_hist = len(seq.prompt) + len(seq.generated) - 1
        n_blocks = (n_hist + self.config.kv_block_size - 1) // self.config.kv_block_size
        bids = seq.alloc.block_ids[:n_blocks]
        pages = self.extract_blocks(bids)
        crcs = None
        if self._integrity is not None:
            # seal-time checksums where the owner can vouch for the block
            # (catches HBM rot between seal and drain); the unsealed tail
            # gets extract-time checksums — wire-scope protection only
            crcs = kv_pages.checksums(pages, self.block_crcs_of(bids))
        return pages, crcs

    def finish_migrated(self, request_id: str, target_instance: str,
                        target_worker: str, mid: str) -> None:
        """The target staged this stream: end it with the in-band re-home
        marker and free the local pages (their contents were copied out).
        MUST run on the engine thread."""
        seq = self._migrating_out.pop(request_id, None)
        if seq is None:
            return
        self.migrated_out_requests += 1
        seq.emit(Annotated.from_data(
            {"migrating": {
                "instance": target_instance, "worker": target_worker,
                "mid": mid, "emitted": len(seq.out_tokens),
            }},
            id=seq.ctx.id,
        ))
        seq.emit(_FINISHED)
        if seq.alloc is not None:
            self.allocator.free_sequence(seq.alloc)
            seq.alloc = None

    def abort_migration(self, request_id: str, reason: str = "") -> None:
        """Migration of a frozen stream failed (transport, target nack, no
        target): end the stream with a resume directive — the client
        degrades to the ordinary resume path (re-admit anywhere, recompute
        softened by the prefix cache). MUST run on the engine thread."""
        seq = self._migrating_out.pop(request_id, None)
        if seq is None:
            return
        self.migrations_failed += 1
        seq.emit(Annotated.from_data(
            {"migrating": {"resume": True, "error": reason}}, id=seq.ctx.id,
        ))
        seq.emit(_FINISHED)
        if seq.alloc is not None:
            self.allocator.free_sequence(seq.alloc)
            seq.alloc = None

    def unfreeze_migrations(self) -> int:
        """Undrained before shipping: frozen sequences re-enter the pending
        queue with allocation and generated history intact — the decode-
        ready re-admission path puts them back in a slot exactly where they
        stopped. MUST run on the engine thread."""
        n = 0
        with self._cond:
            for seq in self._migrating_out.values():
                self._pending.append(seq)
                n += 1
            self._migrating_out.clear()
            if n:
                self._cond.notify()
        return n

    def cut_for_resume(self) -> int:
        """Drain-deadline force-cut: every remaining live stream (slots,
        pending, remote-awaiting, still-frozen) ends with a resume
        directive so the process can exit; clients re-admit elsewhere.
        MUST run on the engine thread."""
        self._drain_inflight()
        cut: List[_Seq] = []
        for i, seq in enumerate(self._slots):
            if seq is not None:
                self._slots[i] = None
                seq.slot = None
                cut.append(seq)
        with self._cond:
            cut.extend(self._pending)
            self._pending.clear()
        cut.extend(self._awaiting.values())
        self._awaiting.clear()
        cut.extend(self._migrating_out.values())
        self._migrating_out.clear()
        for seq in cut:
            seq.emit(Annotated.from_data(
                {"migrating": {"resume": True, "error": "drain deadline"}},
                id=seq.ctx.id,
            ))
            seq.emit(_FINISHED)
            if seq.alloc is not None:
                self.allocator.free_sequence(seq.alloc)
                seq.alloc = None
        return len(cut)

    def live_request_count(self) -> int:
        """Streams this engine still owes an ending (thread-safe)."""
        with self._cond:
            return (
                sum(1 for s in self._slots if s is not None)
                + len(self._pending) + len(self._awaiting)
                + len(self._migrating_out)
            )

    def _migration_ttl(self) -> float:
        ttl = getattr(self, "_staged_ttl", None)
        if ttl is None:
            from dynamo_tpu.disagg.migration import MigrationPolicy

            ttl = self._staged_ttl = MigrationPolicy.from_env().staged_ttl
        return ttl

    def stage_migration(self, meta: dict, pages: kv_pages.Pages) -> dict:
        """Target side: adopt a migrating stream's KV pages ahead of its
        client's re-homed admission. Validates layout, allocates for the
        full N-token history, injects the wire pages over everything the
        local prefix cache doesn't already cover, seals the computed blocks
        into the prefix cache (they are ordinary cluster-visible prefix
        hits from here on), and parks the allocation keyed by migration id
        with ``cached_tokens = N-1`` — the attach then computes exactly one
        fresh position. Any rejection raises BEFORE pool state changes
        beyond a rolled-back allocation: never a torn page set. MUST run on
        the engine thread."""
        self._refuse_for_state("stage_migration")
        toks = [int(t) for t in meta["token_ids"]]
        if len(toks) < 2:
            raise MigrationRejected("history too short to migrate")
        if len(toks) > self.config.max_model_len - 1:
            raise MigrationRejected(
                f"history is {len(toks)} tokens; engine max_model_len is "
                f"{self.config.max_model_len}"
            )
        bs = self.config.kv_block_size
        kv_pages.check(self.cache, pages)
        n_hist = len(toks) - 1
        n_blocks = (n_hist + bs - 1) // bs
        if kv_pages.count(pages) != n_blocks:
            raise MigrationRejected(
                f"page set covers {kv_pages.count(pages)} blocks, history "
                f"needs {n_blocks}"
            )
        tenant = str(meta.get("tenant") or "")
        level = int(meta.get("level") or 0)
        mid = str(meta["mid"])  # parse BEFORE allocating: a malformed
        # checkpoint must not cost pool state
        if self._integrity is not None and meta.get("crcs") is not None:
            # content verification BEFORE any pool state changes: a page
            # set corrupted after the source sealed it (bad HBM there, bad
            # wire hop) raises typed — the nack degrades the stream to the
            # resume path and the SOURCE counts the trip against itself.
            # Never a torn staged entry: nothing was allocated yet.
            kv_pages.verify(pages, meta["crcs"], where="migrate_stage")
        alloc = self.allocator.allocate_sequence(
            toks, wait_inflight=False, tenant=tenant, level=level
        )
        if alloc is None:
            raise MigrationRejected("target out of KV blocks")
        try:
            # local device hits cover the leading cached_tokens//bs blocks;
            # the wire pages fill everything after them. Host-tier hits are
            # dropped: their blocks are freshly-taken single-owner pages the
            # wire content (same tokens, the source's ground-truth KV)
            # overwrites anyway.
            n_dev = alloc.cached_tokens // bs - len(alloc.host_hits)
            alloc.host_hits = []
            if n_dev < n_blocks:
                self.inject_blocks(
                    alloc.block_ids[n_dev:n_blocks],
                    kv_pages.select(pages, slice(n_dev, n_blocks)),
                )
            # seal the computed history: full blocks register in the prefix
            # cache — the migrated prefix is now a cluster-adopted cache
            # entry other requests can hit (ROADMAP item 3's "move the KV"
            # pipe)
            self.allocator.note_tokens_computed(
                alloc, toks[alloc.cached_tokens:n_hist]
            )
        except BaseException:
            # injection/sealing failed past the shape checks (e.g. KV
            # geometry skew the scatter rejects): the nack must not leak
            # the allocation — every drain retry would otherwise bleed the
            # target's pool dry
            self.allocator.free_sequence(alloc)
            raise
        alloc.cached_tokens = n_hist
        self._staged_migrations[mid] = (
            alloc, tuple(toks), time.perf_counter() + self._migration_ttl(),
        )
        with self._cond:
            self._cond.notify()  # wake the idle park so the TTL sweep runs
        return {"mid": mid, "blocks": n_blocks, "cached_tokens": n_hist}

    def _adopt_staged(self, seq: "_Seq") -> None:
        """Admission-time attach: a request carrying a migrate id adopts its
        staged allocation (cached_tokens = N-1 ⇒ prefill computes exactly
        one fresh position). Token mismatch or a missing/expired stage
        falls through to the ordinary resume recompute — the stage-seeded
        blocks still serve as plain prefix hits. Engine thread only."""
        mid = str(seq.request.migrate)
        entry = self._staged_migrations.pop(mid, None)
        if entry is None:
            return
        alloc, toks, _deadline = entry
        if list(toks) != seq.prompt:
            # the client's journal and the source's checkpoint disagree
            # (undelivered tokens at cut time): the staged KV covers a
            # different history — recompute path, blocks back to the cache
            self.allocator.free_sequence(alloc)
            return
        if alloc.tenant != seq.tenant or alloc.level != seq.level:
            self.allocator.retag_sequence(alloc, seq.tenant, seq.level)
        seq.alloc = alloc
        seq.migrated = True
        self.migrated_in_requests += 1

    def _sweep_staged(self) -> None:
        """Free staged migrations whose client never attached (engine
        thread, every loop pass; dict-empty check is the only steady-state
        cost)."""
        if not self._staged_migrations:
            return
        now = time.perf_counter()
        for mid, (alloc, _toks, deadline) in list(
            self._staged_migrations.items()
        ):
            if now > deadline:
                del self._staged_migrations[mid]
                n_blocks = len(alloc.block_ids)
                self.allocator.free_sequence(alloc)
                logger.warning(
                    "staged migration %s expired unclaimed; freed %d blocks",
                    mid, n_blocks,
                )

    def inject_blocks(self, block_ids: List[int], pages: kv_pages.Pages) -> None:
        """Write transferred KV pages into HBM at the given physical pages
        (:func:`kv_pages.put`: a donated update, no cache-sized copy).
        MUST run on the engine thread.

        Accepts host numpy (staged transfers) or jax arrays (the same-host
        device path: pages flow device→device, resharding across meshes —
        including differing tp — handled by XLA at the jit boundary).

        Pages of another layout than the pool's (an int8 pool wants its
        scale tables, a native one none) raise :class:`KvDtypeMismatch`
        before any byte lands."""
        self.cache = kv_pages.put(self.cache, block_ids, pages)

    # -- host KV tier ---------------------------------------------------------

    def _offload_blocks(self, pairs: List[Tuple[int, int, Any]]) -> None:
        """Spill evicted device blocks to the host pool — WITHOUT stalling the
        eviction path (which runs inside admission: a synchronous device_get
        here stalls every decode lane for a host-transfer round trip, W4 of
        the round-2 review; the reference overlaps tier copies with its
        CopyStream, lib/llm/src/kv/layer.rs:100-1132).

        Engine thread only. The gather into fresh device buffers is enqueued
        BEFORE any subsequent dispatch that could overwrite the freed pages
        (single device stream executes in order), so the snapshot is
        consistent; the host copy then rides along asynchronously and is
        harvested by :meth:`_harvest_spills` once ready. ``pairs`` entries
        are ``(hash, block_id, crc)`` — the seal-time content checksum
        rides into the host tier with its block (None with integrity off)."""
        taken = kv_pages.take(self.cache, [bid for _, bid, _ in pairs])
        for a in taken.values():
            a.copy_to_host_async()
        self._pending_spills.append((pairs, taken))

    def _harvest_spills(self, force: bool = False) -> None:
        """Move completed async spills into the host pool (engine thread).
        Non-blocking by default (only entries whose copies are ready);
        ``force`` drains everything (close/idle). A deep backlog is force-
        drained so pending device snapshots can't pile up unboundedly."""
        if not self._pending_spills:
            return
        if len(self._pending_spills) > 8:
            force = True
        while self._pending_spills:
            pairs, taken = self._pending_spills[0]
            if not force and not all(a.is_ready() for a in taken.values()):
                return
            self._pending_spills.popleft()
            # dynlint: allow-host-sync(host-tier spill harvest: only taken
            # once is_ready(), or force-drained while the engine is idle)
            pages = kv_pages.to_host(taken)
            if faults_mod.current() is not None:
                # host-tier leg of the silent-corruption drill: the
                # "corrupt" action bit-flips the spilled copy — bad host
                # RAM; the seal-time crc must catch it at rehit
                first = kv_pages.members(pages)[0]
                pages[first] = faults_mod.corrupt_array(
                    "engine", self._fault_addr, pages[first]
                )
            for i, (h, _, crc) in enumerate(pairs):
                self.host_pool.put(h, kv_pages.block(pages, i), crc=crc)

    def _inject_host_hits(self, alloc: SequenceAllocation) -> None:
        """Load host-tier prefix hits back into the sequence's device pages
        (engine thread only). Runs before any compute touches the sequence."""
        hits = alloc.host_hits
        alloc.host_hits = []
        self.inject_blocks(
            [alloc.block_ids[idx] for idx, _, _, _ in hits],
            kv_pages.stack([block for _, _, block, _ in hits]),
        )

    def complete_remote_prefill(
        self, request_id: str, first_token: int, block_ids: List[int],
        pages: kv_pages.Pages,
    ) -> None:
        """Called (any thread) when a prefill worker's KV lands for a waiting
        sequence: injects pages, registers the prompt KV, emits the first
        token, and queues the sequence for a decode slot. Pages that do not
        fit the pool (a peer without dtype support, a native peer shipping
        into an int8 pool, another block size) fall the request back to
        local prefill instead of writing corrupt pages."""

        def apply():
            seq = self._awaiting.pop(request_id, None)
            if seq is None:
                logger.warning("remote prefill for unknown request %s", request_id)
                return
            # inject only the pages the prefill worker computed (suffix after
            # any prefix-cache hit)
            if block_ids:
                try:
                    self.inject_blocks(block_ids, pages)
                except (KvDtypeMismatch, MigrationRejected) as e:
                    logger.error(
                        "remote prefill for %s: %s — falling back to local "
                        "prefill", request_id, e,
                    )
                    self._awaiting[request_id] = seq
                    self.fail_remote_prefill(request_id, f"pages do not fit: {e}")
                    return
            self.allocator.note_tokens_computed(seq.alloc, seq.prompt[seq.alloc.cached_tokens:])
            seq.first_token_t = time.perf_counter()
            self._emit_token(seq, int(first_token))
            if seq.alloc is not None:  # not finished by the first token
                with self._cond:
                    self._pending.append(seq)
                    self._cond.notify()

        self.post(apply)

    def fail_remote_prefill(self, request_id: str, message: str) -> None:
        """Remote prefill failed: fall back to computing the prefill locally
        (the allocation is still held; seq.remote stays True so _admit won't
        re-dispatch it)."""

        def apply():
            seq = self._awaiting.pop(request_id, None)
            if seq is None:
                return
            logger.warning(
                "remote prefill failed for %s (%s): falling back to local",
                request_id, message,
            )
            with self._cond:
                self._pending.append(seq)
                self._cond.notify()

        self.post(apply)

    def _sweep_remote_timeouts(self) -> None:
        if not self._awaiting:
            return
        now = time.perf_counter()
        for rid, seq in list(self._awaiting.items()):
            if seq.remote_deadline is not None and now > seq.remote_deadline:
                del self._awaiting[rid]
                logger.warning(
                    "remote prefill for %s timed out after %.0fs: prefilling locally",
                    rid, self.config.remote_prefill_timeout,
                )
                with self._cond:
                    self._pending.append(seq)

    def set_event_sink(self, sink: KvEventSink) -> None:
        """Attach/replace the KV event sink (e.g. the distributed publish
        bridge) after construction."""
        self.allocator.set_sink(sink)

    # -- metrics -------------------------------------------------------------

    def metrics_snapshot(self) -> Dict[str, Any]:
        """ForwardPassMetrics-equivalent (reference kv_router/protocols.rs:42-54).

        Taken under the engine condition lock so slot/allocator counters are
        mutually consistent (they feed the KV scheduler's cost function)."""
        with self._cond:
            return self._metrics_locked()

    def _seal_crc_counters(self) -> Dict[str, Any]:
        """The seal-time checksum off the engine thread (cumulative; none
        where no block is checksummed): blocks sealed with one and blocks the
        worker has hashed (equal once nothing is pending), the worker's busy
        time (what the checksum costs; `host_phase_us.seal_crc` is the engine
        thread's part: the hand-over and its waits), the most blocks that
        were ever pending, and the engine thread's waits: as a reader of a
        crc still pending, and at the bound on pending pages."""
        worker = self._crc_worker
        if worker is None:
            return {}
        return {
            "seal_crc_blocks": self.seal_crc_blocks,
            "seal_crc_blocks_offthread": worker.blocks_hashed,
            "seal_crc_worker_us": round(worker.busy_us),
            "seal_crc_pending_peak": worker.pending_peak,
            "seal_crc_reader_waits": self.seal_crc_reader_waits,
            "seal_crc_reader_wait_us": round(self.seal_crc_reader_wait_us),
            "seal_crc_backlog_waits": self.seal_crc_backlog_waits,
        }

    def _attention_tiers(self) -> Dict[str, Dict[str, Any]]:
        """Attention tier per compiled decode/verify variant (dense |
        pallas-v4|v2|v1 | pipeline | chunk-jnp) and whether a kernel in it
        was built in interpret mode. Every decode variant holds the
        engine's one tier; verify scores its K1 positions through
        forward_chunk — the jnp history partial, never the kernel.
        list(dict): one atomic C-level op — the engine thread adds variants
        without holding _cond."""
        name = "{}(lp={},pen={},sample={})".format
        tiers = {name("decode", *k): self._decode_tier
                 for k in list(self._decode_fns)}
        for k in list(self._verify_fns):
            tiers[name("verify", *k)] = {"tier": "chunk-jnp", "interpret": False}
        return tiers

    def _metrics_locked(self) -> Dict[str, Any]:
        active = sum(1 for s in self._slots if s is not None)
        probe = max(self.allocator.probe_tokens, 1)
        m = {
            "request_active_slots": active,
            "request_total_slots": self.config.max_slots,
            "request_total": self.total_requests,  # admitted since boot
            "kv_active_blocks": self.allocator.active_blocks,
            "kv_total_blocks": self.num_blocks,
            # direct admission signals (runtime/admission.py gates on free
            # KV headroom; reclaimable = the warm-cache share of it)
            "kv_free_blocks": self.allocator.free_blocks,
            "kv_reclaimable_blocks": self.allocator.reclaimable_blocks,
            "num_requests_waiting": len(self._pending) + len(self._awaiting),
            "gpu_cache_usage_perc": self.allocator.usage(),
            "gpu_prefix_cache_hit_rate": self.allocator.hit_tokens / probe,
            # the same two since boot, as they are: read by difference
            "prefix_hit_tokens": self.allocator.hit_tokens,
            "prefix_probe_tokens": self.allocator.probe_tokens,
            # the engine thread's time by phase (self time), the part of it in
            # which nothing was in flight while a slot was active, host steps
            # by what they dispatched, the longest stall, all cumulative
            # (runtime/profiling.py:PhaseClock), and start-up's seconds by phase
            **self._clock.snapshot(),
            "queue_wait_us_sum": int(self.queue_wait_us_sum),
            "queue_wait_count": self.queue_wait_count,
            "setup_phase_s": profiling_mod.setup_phase_s(),
            # shared in-flight prefill registry (reserved.rs parity):
            # deferrals onto a concurrent identical prefix + tokens saved
            "inflight_prefill_waits": self.allocator.inflight_waits,
            "shared_prefill_tokens": self.allocator.shared_prefill_tokens,
            # live perf accounting (telemetry plane): a roofline share's
            # inputs as gauges; zeros with sampling off (DYN_TPU_SLO=0)
            "jit_recompiles": compile_count(),
            "kv_peak_occupancy_perc": round(self.allocator.peak_occupancy(), 4),
            # speculative decoding + KV layout (PR7): cumulative draft
            # counters are host-side truth (live with or without telemetry);
            # the EMA acceptance gauge needs perf sampling
            "spec_drafted_tokens": self.spec_drafted_total,
            "spec_accepted_tokens": self.spec_accepted_total,
            "kv_quantized": int(self._kv_quantized),
            # how far the chunk program's history loop engages: tiles read
            # over the tiles of the block tables' full width (cumulative)
            "chunk_history_tiles_read": self.chunk_history_tiles_read,
            "chunk_history_tiles_full": self.chunk_history_tiles_full,
            # the same of the decode program: (lane, tile) slots of history
            # read over the slots of every table's full width (a mesh engine
            # of `models/llama.py`'s programs reads them all)
            "decode_history_tiles_read": self.decode_history_tiles_read,
            "decode_history_tiles_full": self.decode_history_tiles_full,
            # a slot model's own sums (its module's COUNTERS; none otherwise)
            # and the prefix hits it declined for want of the slot's state
            **self.model_counters,
            "slot_state_bytes_a_chip": self._slot_state_bytes_a_chip,
            "prefix_hits_declined": self.prefix_hits_declined,
            # how full the chunk dispatches are (cumulative): positions
            # computed (rows x prefill_chunk) and the prompt tokens among
            # them, rows dispatched and the rows that held a prefilling
            # lane, and how often each rung of the row ladder was taken
            "chunk_positions_dispatched": self.chunk_positions_dispatched,
            "chunk_tokens_fed": self.chunk_tokens_fed,
            "chunk_rows_dispatched": self.chunk_rows_dispatched,
            "chunk_rows_live": self.chunk_rows_live,
            # rows a lane took of a dispatch = chunk_rows_live over the first;
            # chunk dispatches a prompt took = the second over the third
            "chunk_lanes_fed": self.chunk_lanes_fed,
            "prompt_dispatches": self.prompt_dispatches,
            "prompts_prefilled": self.prompts_prefilled,
            "chunk_dispatches_by_rows": {
                # keyed as JSON sends it. .copy(): one atomic C-level op
                # (the engine thread adds rungs without holding _cond)
                str(r): n
                for r, n in sorted(self.chunk_dispatches_by_rows.copy().items())
            },
            # mid-stream resume: re-admissions this engine served (the
            # client-side resume counters live in runtime/resilience.py)
            "resumed_requests": self.resumed_requests,
            # live migration (docs/resilience.md §Live migration): streams
            # shipped out on drain, staged imports a re-homed client
            # adopted, stages currently parked, and — the chaos-gate
            # observable — positions resumed admissions had to recompute
            # (migrated admissions add 0)
            "migrated_out_requests": self.migrated_out_requests,
            "migrated_in_requests": self.migrated_in_requests,
            "migrate_staged": len(self._staged_migrations),
            "resume_recompute_tokens": self.resume_recompute_tokens,
            # integrity plane (docs/resilience.md §Silent corruption):
            # engine-local watchdog trips (the process-global trip/
            # quarantine counters ride attach_kv_publishing)
            "watchdog_trips": self.watchdog_trips,
            "kv_seal_checksums": int(self._seal_checksums),
            **self._seal_crc_counters(),
            "attention_tiers": self._attention_tiers(),
        }
        if self._perf is not None:
            m["decode_tokens_per_s"] = round(self._perf.decode_tps, 3)
            m["step_time_ms"] = round(self._perf.step_time_ms, 3)
            m["batch_slot_util"] = round(self._perf.slot_util, 4)
            m["spec_accept_rate"] = round(self._perf.spec_accept_rate, 4)
        if self._timeline is not None:
            # performance attribution plane (docs/observability.md
            # §Profiling): decode-phase device/host p95 split + device idle
            # fraction, from the process-global dispatch timeline
            m.update(self._timeline.gauges())
        if self._straggler is not None:
            # fail-slow plane (docs/resilience.md §Fail-slow): normalized
            # per-token latency + sample freshness for the aggregator's
            # differential verdict, and this worker's own latched verdict
            # echoed back so the cluster rollup counts suspects from the
            # same stream it ingests
            m.update(self._straggler.gauges())
            m["straggler_state"] = straggler_mod.verdict()
        if self.host_pool is not None:
            m["host_cache_blocks"] = len(self.host_pool)
            m["host_cache_hits"] = self.host_pool.hits
        if self._prefill_budget > 0 or self._fair is not None:
            # chunked-prefill interleaving bound (docs/qos.md): the
            # observable proving the duty cycle works — exported in the
            # single-tenant budget-only mode too
            m["prefill_interleave_max"] = self.prefill_interleave_max
        if self._fair is not None:
            # per-tenant occupancy: what llmctl tenant status and the
            # dynamo_tenant_* cluster gauges render
            tenants: Dict[str, Dict[str, Any]] = {}

            def entry(t: str) -> Dict[str, Any]:
                e = tenants.get(t)
                if e is None:
                    e = tenants[t] = {
                        "class": self._qos.class_name_of(t),
                        "active_slots": 0, "queue_depth": 0, "kv_blocks": 0,
                    }
                return e

            for s in self._slots:
                if s is not None and s.tenant:
                    entry(s.tenant)["active_slots"] += 1
            for s in list(self._pending) + list(self._awaiting.values()):
                if s.tenant:
                    entry(s.tenant)["queue_depth"] += 1
            # .copy(): one atomic C-level op — the engine thread mutates
            # this dict without holding _cond, so iterating it live from
            # the metrics/admission threads could see it resize mid-walk
            for t, n in self.allocator.tenant_blocks.copy().items():
                entry(t)["kv_blocks"] = n
            if tenants:
                m["tenants"] = tenants
        return m


def build_jax_serving_engine(
    card,
    max_batch_size: int = 8,
    kv_block_size: int = 16,
    max_model_len: Optional[int] = None,
    tensor_parallel_size: int = 1,
    num_kv_blocks: Optional[int] = None,
    seed: int = 0,
    event_sink: Optional[KvEventSink] = None,
    decode_steps: int = 4,
    host_cache_blocks: int = 0,
    pipeline_parallel_size: int = 1,
    context_parallel_size: int = 1,
    data_parallel_size: int = 1,
) -> JaxServingEngine:
    """CLI/SDK entry: model + engine from a ModelDeploymentCard."""
    from dynamo_tpu.engine_jax.weights import config_from_card, load_params
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    model_config = config_from_card(card)
    param_shardings = module_for(model_config).param_shardings
    setup = profiling_mod.setup_clock(jax.profiler.TraceAnnotation)
    setup.switch(profiling_mod.S_WEIGHTS)

    mesh = None
    mesh_cfg = MeshConfig(
        dp=data_parallel_size, pp=pipeline_parallel_size,
        tp=tensor_parallel_size, sp=context_parallel_size,
    )
    if mesh_cfg.size > 1:
        mesh = make_mesh(mesh_cfg)
    if mesh is not None and jax.process_count() > 1:
        # process-spanning mesh: every host loads the same full params and
        # materializes only its device shards
        from dynamo_tpu.parallel.multihost_serving import shard_params_global

        params = shard_params_global(
            load_params(card, model_config, seed=seed), model_config, mesh
        )
    else:
        # single host: every leaf is created directly in its sharding — a
        # model that needs the mesh to fit never exists whole on one device
        params = load_params(
            card, model_config, seed=seed,
            shardings=(
                param_shardings(model_config, mesh) if mesh is not None
                else None
            ),
        )

    setup.switch(profiling_mod.S_ENGINE)  # the pool and the state: until `warmup`
    engine_config = EngineConfig(
        max_slots=max_batch_size,
        kv_block_size=kv_block_size,
        max_model_len=max_model_len or min(card.context_length, 4096),
        num_kv_blocks=num_kv_blocks,
        decode_steps=decode_steps,
        host_cache_blocks=host_cache_blocks,
    )
    return JaxServingEngine(
        model_config, params, engine_config, mesh=mesh, event_sink=event_sink
    )
