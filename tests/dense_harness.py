"""What the tests of the dense decoder's tiny preset share (float32 on the CPU;
a helper, not collected): its config, a straight-line greedy reference and a
stream collected through ``generate`` (``tests/test_engine_jax.py``,
``tests/test_engine_multichip.py``); the engine config, traffic and drivers of
``tests/test_chunk_rows.py`` that ``tests/test_seal_crc_worker.py`` serves too;
the page pools of ``tests/test_kv_pages.py`` that it hashes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.models.llama import LLAMA_PRESETS, forward, make_kv_cache
from dynamo_tpu.runtime.engine import Context

from .step_programs import answer, busy, step, submit

CFG = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=128)  # tests/test_engine_jax.py's and its mesh twin's


def reference_greedy(params, prompt, n_steps):
    """Straight-line greedy generation with a private paged cache."""
    cache = make_kv_cache(CFG, 16, 8, dtype=jnp.float32)
    tables = jnp.arange(16, dtype=jnp.int32).reshape(1, 16)
    toks = jnp.asarray([prompt], jnp.int32)
    pos = jnp.arange(len(prompt))[None]
    logits, cache = forward(params, CFG, toks, pos, cache, tables)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n_steps - 1):
        p = len(prompt) + i
        logits, cache = forward(
            params, CFG, jnp.asarray([[out[-1]]], jnp.int32), jnp.asarray([[p]]), cache, tables
        )
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


async def collect_tokens(engine, prompt, max_tokens=8, **sampling):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(**sampling),
    )
    toks = []
    finish = None
    async for item in engine.generate(Context(req)):
        d = item.data
        if d is None:
            continue
        toks.extend(d.get("token_ids", []))
        if d.get("finish_reason"):
            finish = d["finish_reason"]
    return toks, finish


# -- tests/test_chunk_rows.py's engine, traffic and drivers ------------------------------

# ladder [1, 2, 8]: three prefilling lanes already outnumber the small rungs
CHUNK_ROWS_CFG = EngineConfig(
    max_slots=8, kv_block_size=8, max_model_len=160, prefill_chunk=16, decode_steps=4
)


def prompt_of(n, salt):  # the dense tiny preset's vocabulary (1 .. 97), not the model files' 96
    return [(salt * 31 + 7 * i + 3) % 97 + 1 for i in range(n)]


def mesh_engine(params, engine_cfg=CHUNK_ROWS_CFG, **axes):
    from dynamo_tpu.models.llama import param_shardings
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(**axes))
    return JaxServingEngine(
        CFG, jax.device_put(params, param_shardings(CFG, mesh)), engine_cfg, mesh=mesh
    )


# (arrives at host step, prompt tokens, answer tokens, sampling): prompts of one
# to five chunks, so that lanes prefill beside lanes that decode in most steps
MIXED = [
    (0, 9, 28, {}), (2, 40, 12, {}), (2, 70, 9, {}), (3, 17, 14, {}),
    (5, 33, 10, {}), (9, 16, 6, {}), (9, 50, 8, {}),
]


def serve_schedule(eng, schedule, on_step=None, salt=0):
    seqs, t = {}, 0
    while busy(eng) or len(seqs) < len(schedule):
        for i, (at, n, m, sampling) in enumerate(schedule):
            if at == t:
                seqs[i] = submit(eng, prompt_of(n, salt + i), m, **sampling)
        if on_step is not None:
            on_step(t, seqs)
        step(eng)
        t += 1
        assert t < 400
    return [answer(seqs[i]) for i in range(len(schedule))]


# -- tests/test_kv_pages.py's pools ---------------------------------------------------------

N_BLOCKS, BLOCK = 12, 8
POOLS = ["native", "int8", "latent"]


def filled(shape, dtype, salt):
    """Every element its own value, the same on every numpy."""
    flat = (np.arange(int(np.prod(shape)), dtype=np.int64) * 37 + salt * 101) % 251
    return (flat - 125).reshape(shape).astype(dtype)


def pool(kind, block=BLOCK):
    """A pool with something in every row. The third kind is the native pool
    and one more member, of another rank and dtype."""
    shapes = jax.eval_shape(
        lambda: make_kv_cache(CFG, N_BLOCKS, block, quantized=kind == "int8")
    )
    pool = {
        m: jnp.asarray(filled(a.shape, a.dtype, i))
        for i, (m, a) in enumerate(sorted(shapes.items()))
    }
    if kind == "latent":
        pool["latent"] = jnp.asarray(
            filled((CFG.num_layers, N_BLOCKS, block, 64), np.float32, 9)
        )
    return pool
