"""The rules of ``tests/step_programs.py``, each held by a case that fails when
the rule is dropped: a key has ONE program and a second call compiles nothing;
a patch made through ``patched`` shows in the program and is gone from the
next one; the forced tokens as an operand give what the closure gave; JAX keys
a trace on the matmul precision, so a program is refused outside it. On the
smallest module there is: Kimi-Linear's tiny shape cut to two layers."""

import contextlib
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.models import kimi_linear as kl

from . import step_programs
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    card, chunk_program, decode_program, highest_precision, patched, prompt_of,
)

SHAPE = {
    "model_type": "kimi_linear", "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "linear_attn_num_heads": 2, "linear_attn_head_dim": 8, "short_conv_kernel_size": 4,
    "kda_layers": [1], "full_attn_layers": [2],
    "first_k_dense_replace": 1, "moe_intermediate_size": 16, "num_experts": 4,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "moe_renormalize": True, "rms_norm_eps": 1e-5,
    "vocab_size": 96, "tie_word_embeddings": False,
}
SLOTS, C, BS, MB, SLOT = 4, 16, 8, 4, 2


@contextlib.contextmanager
def xla_compiles():
    """The programs XLA compiles inside the block, by name (``jax_log_compiles``)."""
    seen = []

    class Listener(logging.Handler):
        def emit(self, record):
            if record.getMessage().startswith("Finished XLA compilation of"):
                seen.append(record.getMessage().split()[4])

    listener = Listener()
    logging.getLogger("jax").addHandler(listener)
    try:
        with jax.log_compiles():
            yield seen
    finally:
        logging.getLogger("jax").removeHandler(listener)


def a_config():
    return config_from_card(card(SHAPE), jnp.float32)  # a NEW object a call, equal by its fields


@pytest.fixture(scope="module")
def params():
    return kl.init_params(jax.random.PRNGKey(3), a_config())


def chunk_operands(n=11, salt=1):
    """One chunk dispatch's operands: ``n`` tokens of slot ``SLOT`` in row 0, a padding row under it."""
    cfg = a_config()
    toks, pos = np.zeros((2, C), np.int32), np.full((2, C), -1, np.int32)
    toks[0, :n], pos[0, :n] = prompt_of(n, salt), np.arange(n)
    tables = np.zeros((2, MB), np.int32)
    tables[0] = np.arange(1, 1 + MB)
    return (jnp.asarray(toks), jnp.asarray(pos), kl.make_kv_cache(cfg, 1 + MB, BS), jnp.asarray(tables),
            kl.make_slot_state(cfg, SLOTS), jnp.asarray([SLOT, SLOTS], jnp.int32))


def test_a_key_has_one_program_and_a_second_call_compiles_nothing(params):
    """Two configs built apart (equal by their fields) and two sets of
    operands of one geometry: the same jitted function, one compile; another
    static argument or another module's name is another key."""
    def mine():  # a key no other case asks for, whichever of them this worker ran before
        return dataclasses.replace(a_config(), rms_norm_eps=3e-5)

    first, second = chunk_operands(), chunk_operands(n=7, salt=2)
    with xla_compiles() as compiled:
        chunk = chunk_program(kl, mine())
        h, *_ = chunk(params, *first)
    assert len(compiled) == 1, compiled
    with xla_compiles() as compiled:
        again = chunk_program(kl, mine())
        h2, *_ = again(params, *second)
    assert again is chunk and not compiled, compiled
    assert np.abs(np.asarray(h[0, :7]) - np.asarray(h2[0, :7])).max() > 1e-3  # and it is no constant
    assert decode_program(kl, a_config(), 2, 31) is decode_program(kl, a_config(), 2, 31)
    assert decode_program(kl, a_config(), 3, 31) is not decode_program(kl, a_config(), 2, 31)
    assert decode_program(kl, a_config(), 2, 31) is not chunk


def test_a_patch_made_through_the_helper_shows_and_the_next_program_is_the_unpatched_one(params, monkeypatch):
    """The kept program was traced before the patch: a plain ``setattr`` does
    not reach it (what the rule is there for), ``patched`` does, the program
    built under it is kept nowhere, and with the patch undone the kept program
    answers again, with nothing compiled."""
    cfg = a_config()
    kept = chunk_program(kl, cfg)
    want = np.asarray(kept(params, *chunk_operands())[0])
    norm = kl.rms_norm

    def doubled(x, w, eps):
        return 2.0 * norm(x, w, eps)

    monkeypatch.setattr(kl, "rms_norm", doubled)
    assert chunk_program(kl, cfg) is kept  # out of the harness's sight: the stale program
    np.testing.assert_array_equal(np.asarray(kept(params, *chunk_operands())[0]), want)
    monkeypatch.undo()

    patched(monkeypatch, kl, "rms_norm", doubled)
    fresh = chunk_program(kl, cfg)
    assert fresh is not kept and chunk_program(kl, cfg) is fresh  # one program for as long as the patch holds
    got = np.asarray(fresh(params, *chunk_operands())[0])
    assert np.abs(got - want).max() > 1e-2
    monkeypatch.undo()

    operands = chunk_operands()
    with xla_compiles() as compiled:
        assert chunk_program(kl, cfg) is kept and kl.rms_norm is norm
        got = chunk_program(kl, cfg)(params, *operands)[0]
    np.testing.assert_array_equal(np.asarray(got), want)
    assert not compiled, compiled
    assert all(program is not fresh for program in step_programs._kept.values())


def test_forcing_as_an_operand_gives_bit_for_bit_what_the_closure_gave(params):
    """Three teacher-forced decode steps behind a chunk of 11 tokens: the
    kept program, its forced tokens an operand a table's positions wide,
    against ``decode`` called as the files called it (a closure over the
    sequence's own tokens, eagerly): the steps' logits, the positions, the
    state and the pages, bit for bit. Other tokens through the same program
    compile nothing and give other logits."""
    cfg, n, steps = a_config(), 11, 3
    toks, pos, cache, tables, state, lanes = chunk_operands(n)
    _, cache, state, _ = chunk_program(kl, cfg)(params, toks, pos, cache, tables, state, lanes)
    tokens = np.asarray(prompt_of(n + steps, 1), np.int32)
    lanes_tables = np.zeros((SLOTS, MB), np.int32)
    lanes_tables[SLOT] = np.asarray(tables[0])
    first, at = np.zeros((SLOTS,), np.int32), np.full((SLOTS,), -1, np.int32)
    first[SLOT], at[SLOT] = tokens[n], n

    def forced(logits, p, carry, k):  # the closure form
        return jnp.where(p >= 0, jnp.asarray(tokens)[jnp.clip(p + 1, 0, len(tokens) - 1)], 0), carry, logits

    want = kl.decode(params, cfg, jnp.asarray(first), jnp.asarray(at), cache, jnp.asarray(lanes_tables), state,
                     steps, BS * MB - 1, forced, None)
    forcing = np.zeros((SLOTS, BS * MB), np.int32)
    forcing[SLOT, :len(tokens)] = tokens
    decode = decode_program(kl, cfg, steps, BS * MB - 1)
    got = decode(params, jnp.asarray(first), jnp.asarray(at), cache, jnp.asarray(lanes_tables), state,
                 jnp.asarray(forcing))
    assert int(got[1][SLOT]) == n + steps
    for mine, theirs in zip(jax.tree.leaves(got[1:]), jax.tree.leaves(want[1:])):  # [0]: the token behind the last step
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    forcing[SLOT, n + 1] = (forcing[SLOT, n + 1] + 1) % 95 + 1
    with xla_compiles() as compiled:
        other = decode(params, jnp.asarray(first), jnp.asarray(at), cache, jnp.asarray(lanes_tables), state,
                       jnp.asarray(forcing))
    assert not compiled, compiled
    np.testing.assert_array_equal(np.asarray(other[3][0]), np.asarray(got[3][0]))  # the first step saw the same token
    assert np.abs(np.asarray(other[3][1, SLOT]) - np.asarray(got[3][1, SLOT])).max() > 1e-3


def test_jax_keys_a_trace_on_the_matmul_precision_so_a_program_is_refused_outside_it(params):
    """The same jitted function called under another default precision traces
    and compiles again (the key the docstring speaks of), which under ONE
    harness key would be a second, coarser program nobody asked for: asked for
    outside ``highest_precision``, the harness refuses."""
    cfg = a_config()
    chunk, operands = chunk_program(kl, cfg), chunk_operands()
    chunk(params, *operands)
    with xla_compiles() as compiled:
        chunk(params, *operands)
    assert not compiled, compiled
    with jax.default_matmul_precision("bfloat16"), xla_compiles() as compiled:
        chunk(params, *operands)
        with pytest.raises(AssertionError, match="highest_precision"):
            chunk_program(kl, cfg)
    assert len(compiled) == 1, compiled
