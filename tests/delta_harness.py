"""What ``tests/test_delta_lane_rows.py`` drives the two delta-rule modules
with (``models/kimi_linear.py``, ``models/qwen3_next.py``; a helper, not
collected), and what it shares with ``tests/test_kimi_linear.py`` and
``tests/test_qwen3_next.py``: the two tiny shapes and Qwen3-Next's seeded
weights; the recurrence's inputs as the two mixers make them, and the harness
that feeds chunk dispatches and decode steps through
``tests/step_programs.py``'s kept programs
(``tests/jamba_harness.py:dispatch_rows``'s form, for any module whose pool's
members are ``[L, N, bs, ...]``)."""

import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine_jax.weights import config_from_card

from .step_programs import card, chunk_program, decode_program, prompt_of

KIMI_SHAPE = {
    "model_type": "kimi_linear", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "linear_attn_num_heads": 2, "linear_attn_head_dim": 16, "short_conv_kernel_size": 4,
    "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
    "first_k_dense_replace": 1, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_published": 8, "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "moe_renormalize": True, "rms_norm_eps": 1e-5,
    "vocab_size": 96, "tie_word_embeddings": False,
}
QWEN3_NEXT_SHAPE = {
    "model_type": "qwen3_next", "hidden_size": 64, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 4, "num_experts_published": 16,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6, "vocab_size": 96,
    "tie_word_embeddings": False, "max_position_embeddings": 262144,
}
# float32 on the CPU at the highest matmul precision on both sides: the two files' own tolerances
# (tests/test_kimi_linear.py, tests/test_qwen3_next.py say what each allows and why)
MODELS = {
    "kimi_linear": dict(shape=KIMI_SHAPE, atol=2e-4, prefix="kda", layers=4),
    "qwen3_next": dict(shape=QWEN3_NEXT_SHAPE, atol=5e-4, prefix="gdn", layers=3),
}
SPARE_BLOCKS = 8


def louder_qwen3_next(params):
    """Qwen3-Next's seeded weights with the zero-centred norm weights and the
    router large enough to tell (normal x 0.02 as published would hide a plain
    ``w`` in ``1 + w``'s place only by a little; a flat router no choice)."""
    def louder(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("mixer_norm", "ffn_norm", "q_norm", "k_norm", "final_norm"):
            return a * 15.0
        return a * 50.0 if name == "router" else a

    return jax.tree_util.tree_map_with_path(louder, params)


def model_of(name):
    """(the module, its plain reference, the tiny shape, the config, seeded
    weights, the tolerance, the counters' prefix, the layers that keep a
    state) of one of ``MODELS``."""
    module = importlib.import_module(f"dynamo_tpu.models.{name}")
    cfg = config_from_card(card(MODELS[name]["shape"]), jnp.float32)
    params = module.init_params(jax.random.PRNGKey(3), cfg)
    if name == "qwen3_next":
        params = louder_qwen3_next(params)
    return types.SimpleNamespace(
        name=name, module=module, ref=importlib.import_module(f"benchmark.reference_{name}"),
        cfg=cfg, params=params, **MODELS[name])


def recurrence_inputs(rows, t, decay, h=4, d=16, seed=0):
    """Inputs as the two mixers make them (unit keys, queries of length ``d **
    -0.5``, beta in (0, 1)) and a carried state; the log-decay ``"a_channel"``
    (Kimi-Linear's KDA) or ``"a_head"``, one number spread over the head's key
    channels (Qwen3-Next's Gated DeltaNet)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (rows, t, h, d), jnp.float32) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_decay = jax.random.uniform(
        ks[3], (rows, t, h, d if decay == "a_channel" else 1), jnp.float32, -1.6, -0.001)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, t, h), jnp.float32))
    return ((q, k, v, jnp.broadcast_to(log_decay, q.shape), beta),
            jax.random.normal(ks[5], (rows, h, d, d), jnp.float32))


def dispatch_rows(model, dispatches, rows=8, slots=10, mb=8, n_decode=3, salt=None):
    """Chunk dispatches of ``rows`` rows over ``slots`` slots, then ``n_decode``
    teacher-forced decode steps of every slot fed, off the state and pages the
    dispatches left. A dispatch is a list of its rows in order, ``(slot, n)``
    = the slot's next ``n`` prompt tokens (a lane's rows of one dispatch are
    its successive pieces) or ``None`` = a padding row; the rows left are
    padding. The k-th slot fed has blocks ``1 + k * mb`` onwards, and the pool
    holds ``SPARE_BLOCKS`` more that no table names: no page but those a fed
    slot's tokens reach may be written, block 0 (where a padding row's table
    points) and the spare ones included, which is held here for every caller.
    Every slot's state starts stale; its tokens are ``prompt_of(., salt or the
    slot)``. Returns ({slot: (its tokens, logits ``[prompt + n_decode, V]``)},
    state, cache, the dispatches' counters)."""
    mod, cfg, params = model.module, model.cfg, model.params
    c, bs = 16, 8
    fed = list(dict.fromkeys(row[0] for d in dispatches for row in d if row))
    length = {slot: sum(row[1] for d in dispatches for row in d if row and row[0] == slot) for slot in fed}
    toks_of = {slot: np.asarray(prompt_of(length[slot] + n_decode, salt=salt or slot), np.int32) for slot in fed}
    table = {slot: 1 + k * mb + np.arange(mb, dtype=np.int32) for k, slot in enumerate(fed)}
    cache = mod.make_kv_cache(cfg, 1 + len(fed) * mb + SPARE_BLOCKS, bs)
    state = jax.tree.map(lambda a: a + 7.0, mod.make_slot_state(cfg, slots))  # stale, every slot
    at, logits, sums = dict.fromkeys(fed, 0), {slot: [] for slot in fed}, []
    chunk = chunk_program(mod, cfg)
    for d in dispatches:
        toks, pos = np.zeros((rows, c), np.int32), np.full((rows, c), -1, np.int32)
        tables, lanes = np.zeros((rows, mb), np.int32), np.full((rows,), slots, np.int32)
        for r, row in enumerate(d):
            if row is None:
                continue
            slot, n = row
            toks[r, :n], pos[r, :n] = toks_of[slot][at[slot]:at[slot] + n], np.arange(at[slot], at[slot] + n)
            tables[r], lanes[r] = table[slot], slot
            at[slot] += n
        h, cache, state, counted = chunk(
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables),
            state, jnp.asarray(lanes))
        for r, row in enumerate(d):
            if row is not None:
                logits[row[0]].append(np.asarray(mod.lm_head(params, cfg, h[r, :row[1]]), np.float32))
        sums.append(dict(zip(mod.COUNTERS, np.asarray(counted).tolist())))
    if n_decode:
        lanes_tables = np.zeros((slots, mb), np.int32)
        toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
        forcing = np.zeros((slots, bs * mb), np.int32)  # a table's positions wide: one program a geometry
        for slot in fed:
            lanes_tables[slot], toks[slot], pos[slot] = table[slot], toks_of[slot][length[slot]], length[slot]
            forcing[slot, :len(toks_of[slot])] = toks_of[slot]
        out = decode_program(mod, cfg, n_decode, bs * mb - 1)(  # teacher forcing: each sequence's own next token
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
        assert [int(out[1][slot]) for slot in fed] == [length[slot] + n_decode for slot in fed]
        for slot in fed:
            logits[slot].append(np.asarray(out[3], np.float32)[:, slot])
        state, cache = out[5], out[4]
    reached = np.zeros((next(iter(cache.values())).shape[1],), bool)
    for slot in fed:
        reached[table[slot][:-(-(length[slot] + n_decode) // bs)]] = True
    for name, pool in cache.items():
        pool = np.asarray(pool, np.float32)
        assert not pool[:, ~reached].any(), f"{name}: a page outside what the fed slots' tokens reach was written"
        assert all(pool[:, block].any() for block in np.flatnonzero(reached)), name
    return {slot: (toks_of[slot], np.concatenate(logits[slot])) for slot in fed}, state, cache, sums
