"""Who owns a compiled step program in the tests of the model-module contract:
this module, and nobody else (a helper, not collected).

A model module's ``forward_chunk``, ``draft_chunk`` and ``decode`` called
eagerly re-lower and re-compile every ``lax.scan`` / ``fori_loop`` / ``cond``
inside them on every call (a fresh closure is a fresh cache key), and a
``jax.jit(lambda ...)`` written inside a helper is a new jitted function a call.
So a test file asks HERE for its programs: ``chunk_program``, ``draft_program``,
``decode_program`` and ``reference_program`` each give ONE ``jax.jit``-ted function a key ``(module,
config, static arguments)``, built on first use and kept for the life of the
worker process, with the weights and every array an operand. The configs are
frozen dataclasses and hash by their fields, so a config a fixture builds again
finds the program the first one built.

The matmul precision: every file that calls here sets
``jax.default_matmul_precision("highest")`` for its module
(``highest_precision`` below). JAX keys a jitted function's trace on that
context (it is part of ``jax.config``'s trace context), so a kept program called
outside it would quietly trace and compile a second, coarser program under the
same key; a program is therefore refused outside the context
(``tests/test_step_programs.py`` holds both).

A case that PATCHES the module under test, an op it calls (``ops/moe.py``,
``ops/mhc.py``) or a constant that shapes a program (``TOKENS_AT_ONCE``) goes
through ``patched``: a kept program was traced before the patch and would not
see it, and one traced under the patch must not be served to the next test.
A case that patches a plain reference (``benchmark/reference_*.py``) calls it
directly and not through ``reference_program``; patches of the host's
``DeviceDrafter`` reach no program and need nothing.

Beside the programs, what the files used to copy or take from each other:
``card``, ``published_shape``, ``prompt_of``, ``highest_precision``, the engine
driven a host step at a time on the test's thread (``submit`` ... ``served``)
or through ``generate`` (``collect``), ``MIXED``."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine_jax.engine import _FINISHED, _Seq
from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.runtime.engine import Context

# -- the programs -------------------------------------------------------------------

_kept: dict = {}  # key -> the jitted function; `patched` puts an empty one in its place for as long as it holds


def _program(key, fn):
    assert jax.config.jax_default_matmul_precision == "highest", (
        "a step program is traced and called under `highest_precision`: JAX keys the trace on it")
    if key not in _kept:
        _kept[key] = jax.jit(fn)
    return _kept[key]


def _key(kind, module, cfg, *static, **named):
    return (kind, module.__name__, cfg, *static, *sorted(named.items()))


def chunk_program(module, cfg, **static):
    """``module.forward_chunk`` as the engine runs it: ``(params, tokens,
    positions, cache, tables, state, lanes) -> (hidden, cache, state,
    counters)``; ``static`` is what the module takes beside them (``raw=True``)."""
    return _program(_key("chunk", module, cfg, **static),
                    lambda params, *operands: module.forward_chunk(params, cfg, *operands, **static))


def draft_program(module, cfg):
    """``module.draft_chunk``: ``(params, hidden, next tokens, positions,
    cache, tables) -> (hidden, cache, counters)``."""
    return _program(_key("draft", module, cfg),
                    lambda params, *operands: module.draft_chunk(params, cfg, *operands))


def teacher_forcing(forcing):
    """The engine's ``sample`` for a decode program that is told its tokens:
    lane ``s`` at position ``p`` is given ``forcing[s, p + 1]`` next, and the
    step's logits are its output. ``forcing`` None: the lane's own first choice."""
    def sample(logits, positions, carry, k):
        if forcing is None:
            return jnp.argmax(logits, -1).astype(jnp.int32), carry, logits
        slots, known = forcing.shape
        following = forcing[jnp.arange(slots), jnp.clip(positions + 1, 0, known - 1)]
        return jnp.where(positions >= 0, following, 0), carry, logits

    return sample


def decode_program(module, cfg, steps, last_position, **static):
    """``module.decode`` for ``steps`` teacher-forced steps: ``(params, tokens,
    positions, cache, tables, state, forcing) -> module.decode's tuple`` with
    the steps' logits ``[steps, S, V]`` as the stacked outputs. The forced
    tokens are an OPERAND (``forcing`` ``[S, T]`` int32, or None for greedy
    steps; a closure over them would be a program a call), so the decode steps
    of one geometry compile once. ``static``: ``draft=True`` where a module has it."""
    def decode(params, tokens, positions, cache, tables, state, forcing):
        return module.decode(params, cfg, tokens, positions, cache, tables, state, steps, last_position,
                             teacher_forcing(forcing), None, **static)

    return _program(_key("decode", module, cfg, steps, last_position, **static), decode)


def reference_program(ref, shape, output="logits"):
    """A plain reference's ``logits`` (or ``draft_logits``) over ``shape`` as ONE
    program a length: ``(params, tokens, positions) -> [len(positions), V]``.
    The reference is the same function; called eagerly it compiles every
    operation at every new length and every ``lax.scan`` in it on every call.
    A case that patches the REFERENCE with a plain ``setattr``, or hands it a
    ``dot`` of its own, calls it directly, as it is written in ``benchmark/``."""
    return _program(("reference", ref.__name__, output, json.dumps(shape, sort_keys=True)),
                    lambda params, tokens, positions: getattr(ref, output)(params, shape, tokens, positions))


def patched(monkeypatch, target, name, value):
    """``monkeypatch.setattr(target, name, value)`` for a patch that a step
    program's trace would read. For as long as the patch holds (until the test
    ends or calls ``monkeypatch.undo()``) the programs asked for are built
    afresh, and none of them is kept past it: the kept ones come back with the
    attribute."""
    monkeypatch.setattr(target, name, value)
    monkeypatch.setattr(sys.modules[__name__], "_kept", {})


# -- what the files share beside them -----------------------------------------------

def card(shape):
    """A model card that holds a config and no weights, as ``config_from_card`` reads it."""
    return types.SimpleNamespace(model_config=shape, model_path=None, gguf_path=None)


def published_shape(model_type):
    """The card of ``benchmark/configs/`` whose ``model_type`` this is: a card
    ``config_from_card`` reads, for a test that needs SOME card of another
    module and none of that module's tests."""
    configs = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs")
    for name in sorted(os.listdir(configs)):
        with open(os.path.join(configs, name)) as f:
            shape = json.load(f)
        if shape.get("model_type") == model_type:
            return shape
    raise KeyError(model_type)


def prompt_of(n, salt=0):
    """``n`` tokens of the tiny shapes' vocabulary of 96 (1 .. 95), another prompt a ``salt``."""
    return [(salt * 31 + 7 * i + 3) % 95 + 1 for i in range(n)]


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    """Float32 products at float32's precision on both sides of a comparison:
    a file imports this fixture, and it holds for the file's tests."""
    with jax.default_matmul_precision("highest"):
        yield


# (the step a request is submitted on, prompt tokens, answered): a prompt of 7 chunks beside lanes that
# decode, prompts of 1 to 9 chunks at once (more rows than the second rung holds: the pieces left go on
# in the next step), a late long one behind decoding lanes
MIXED = [(0, 9, 24), (2, 100, 8), (2, 12, 10), (3, 60, 6), (3, 140, 5), (3, 37, 9), (3, 90, 5),
         (4, 128, 6), (4, 16, 7), (9, 75, 5)]


class _Inline:
    """Stands where a request's event loop does: items land in its queue at once."""

    def is_closed(self):
        return False

    def call_soon_threadsafe(self, fn, *args):
        fn(*args)


# The engine driven one host step at a time on the test's own thread (``_admit`` + ``_dispatch_step``,
# what ``_step_loop`` runs; no engine thread is started).

def submit(eng, prompt, max_tokens, **sampling):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(**sampling),
    )
    seq = _Seq(Context(req), req, _Inline())
    eng._pending.append(seq)
    return seq


def step(eng):
    eng._admit()
    eng._dispatch_step()


def busy(eng):
    return bool(eng._pending or any(eng._slots) or eng._inflight is not None)


def run_out(eng, limit=400):
    for _ in range(limit):
        if not busy(eng):
            return
        step(eng)
    raise AssertionError("the engine did not come to rest")


def answer(seq):
    """(tokens, log-probabilities, finish reason) of everything emitted so far."""
    toks, lps, finish = [], [], None
    while not seq.out_queue.empty():
        item = seq.out_queue.get_nowait()
        if item is _FINISHED:
            continue
        d = item.data or {}
        toks.extend(d.get("token_ids", []))
        lps.extend(d.get("log_probs") or [])
        finish = d.get("finish_reason") or finish
    return toks, lps, finish


def served(engine, prompt, max_tokens, **sampling):
    """A request served with nothing beside it: ``answer`` of it."""
    seq = submit(engine, prompt, max_tokens, **sampling)
    run_out(engine)
    return answer(seq)


async def collect(engine, prompt, max_tokens=20, with_lp=False, **sampling):
    """The same through ``engine.generate`` on the engine's own thread: (tokens,
    log-probabilities, finish reason)."""
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(logprobs=2 if with_lp else None, **sampling),
    )
    toks, lps, finish = [], [], None
    async for item in engine.generate(Context(req)):
        d = item.data or {}
        toks.extend(d.get("token_ids", []))
        lps.extend(d.get("log_probs") or [])
        if d.get("finish_reason"):
            finish = d["finish_reason"]
    return toks, lps, finish
