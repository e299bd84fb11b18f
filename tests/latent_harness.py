"""What the tests of the two latent-attention modules that keep nothing per
slot share (``tests/test_openpangu.py``, ``tests/test_xing4.py``,
``tests/test_latent_lane_rows.py``; a helper, not collected): the two tiny
shapes, the geometry, one chunk dispatch of a prompt's next tokens and a lane's
rows of one dispatch, both through ``tests/step_programs.py``'s kept programs."""

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import openpangu as op

from .step_programs import chunk_program, decode_program, draft_program, patched, prompt_of, reference_program

# ATOL: float32 on the CPU, at the highest matmul precision on both sides. The
# program and the reference order their sums differently (absorbed against
# expanded latent attention, the experts' rows batched against every token
# through every expert, a chunk against the whole sequence): 2e-4 on logits of
# magnitude 4 is what tests/test_kimi_linear.py allows for the same reasons
# (measured here: 4e-6). A wrong page, rotation, norm or expert moves a logit
# by 1e-2 and more, and bfloat16 where float32 is stated by 3e-2
# (tests/test_openpangu.py:test_bfloat16_in_float32s_place_would_fail).
ATOL = 2e-4


OPENPANGU_SHAPE = {
    "model_type": "pangu_ultra_moe", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 25600000, "max_position_embeddings": 131072,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "num_nextn_predict_layers": 1, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "attention_bias": False, "tie_word_embeddings": False, "vocab_size": 96,
}
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64, "type": "yarn"}
XING4_SHAPE = {
    "model_type": "xing4_0", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 10000, "max_position_embeddings": 262144, "rope_scaling": YARN,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.0, "num_nextn_predict_layers": 1, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "ep_size": 1,
    "attention_bias": False, "tie_word_embeddings": False, "vocab_size": 96,
}
BS, MB, C = 8, 8, 16


def feed(mod, cfg, params, cache, tokens, start, n, table, *, drafting=False, following=None):
    """One chunk dispatch of ``n`` tokens from ``start`` in row 0 (row 1 is
    padding) through ``mod``'s programs: (logits ``[n, V]``, the prediction
    module's logits or None, the pool, sums)."""
    toks, pos = np.zeros((2, C), np.int32), np.full((2, C), -1, np.int32)
    toks[0, :n], pos[0, :n] = tokens[start:start + n], np.arange(start, start + n)
    tables = np.zeros((2, MB), np.int32)
    tables[0] = table
    x, cache, state, sums = chunk_program(mod, cfg, raw=True)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables), None, jnp.asarray([0, 4], jnp.int32))
    assert state is None and x.shape == (2, C, cfg.hidden_size)  # ONE stream leaves the program
    logits = mod.lm_head(params, cfg, mod.final_norm(params, cfg, x)[0, :n])
    drafts = None
    if drafting:
        nxt = np.zeros((2, C), np.int32)
        nxt[0, :n] = following[start:start + n]
        hd, cache, more = draft_program(mod, cfg)(
            params, x, jnp.asarray(nxt), jnp.asarray(pos), cache, jnp.asarray(tables))
        drafts, sums = mod.lm_head(params, cfg, hd[0, :n]), sums + more
    return logits, drafts, cache, np.asarray(sums)



def check_lanes_decode_as_each_does_alone(mod, cfg, params, drafting):
    """Two lanes of one decode dispatch, the LONGER in the higher slot (the
    live form takes the lanes longest first, so both move) and an idle slot
    between and beside them: each lane's logits, its drafts and what it leaves
    in the pool are what it gives alone in a dispatch of its own, and the
    program's counters say what it read (every slot's whole table once, by
    the gather, and a step's row against the block's one tile of this table
    and the dispatch's steps; ``decode_history_tiles`` is the host's count of
    the tiles)."""
    n_decode, slots, lengths = 3, 4, {1: 21, 3: 45}
    cache = mod.make_kv_cache(cfg, 32, BS, drafting=drafting)
    tokens = {slot: np.asarray(prompt_of(n + n_decode + 1, salt=slot), np.int32) for slot, n in lengths.items()}
    tables = np.zeros((slots, MB), np.int32)
    for slot, n in lengths.items():
        tables[slot] = 1 + (slot // 2) * MB + np.arange(MB)
        for at in range(0, n, C):
            _, _, cache, _ = feed(mod, cfg, params, cache, tokens[slot], at, min(C, n - at), tables[slot],
                                  drafting=drafting, following=tokens[slot][1:])
    forcing = np.zeros((slots, BS * MB), np.int32)
    for slot in lengths:
        forcing[slot, :len(tokens[slot])] = tokens[slot]
    program = decode_program(mod, cfg, n_decode, 95, **({"draft": True} if drafting else {}))

    def dispatch(fed):
        toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
        for slot in fed:
            toks[slot], pos[slot] = tokens[slot][lengths[slot]], lengths[slot]
        out = program(params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables), None,
                      jnp.asarray(forcing))
        return out, dict(zip(mod.COUNTERS, np.asarray(out[6]).tolist())), pos

    both, counts, pos = dispatch(lengths)
    n_hist = cfg.num_layers + bool(drafting)
    tile = BS * MB  # this table is one tile
    assert mod.decode_history_tiles(pos, BS, MB) == slots  # one block of four lanes, one tile
    gathered = n_hist * slots * tile  # once a dispatch: every slot's whole table
    assert counts["mla_history_positions_read"] == gathered + n_hist * n_decode * len(lengths) * (tile + n_decode)
    assert counts["mla_history_positions_live"] == n_hist * sum(
        n + k + 1 for n in lengths.values() for k in range(n_decode))
    for slot, n in lengths.items():
        alone, counts, _ = dispatch([slot])
        assert counts["mla_history_positions_read"] == gathered + n_hist * n_decode * (tile + n_decode)
        np.testing.assert_allclose(np.asarray(both[3])[:, slot], np.asarray(alone[3])[:, slot], atol=ATOL)
        assert int(both[1][slot]) == int(alone[1][slot]) == n + n_decode
        if drafting:
            assert int(both[7][slot]) == int(alone[7][slot])
        pages = tables[slot][n // BS:(n + n_decode - 1) // BS + 1]
        np.testing.assert_allclose(np.asarray(both[4]["latent"])[:, pages], np.asarray(alone[4]["latent"])[:, pages],
                                   atol=ATOL)
        assert np.asarray(both[4]["latent"])[:, pages].any()


# Successive pieces of a prompt in consecutive rows of ONE chunk dispatch. ``dispatches``: each a list of
# rows in order, (lane, valid tokens) or None (a padding row: no lane's, its table all zeros); a lane's
# rows go on where its last one ended, dispatch after dispatch. ``at_once``: rows of a group of
# ``_in_groups`` (the served 128-token chunk has 4; here TOKENS_AT_ONCE is set so that a lane's rows lie
# in several groups). ``hits``: lane -> (the lane whose first tokens and blocks it shares, how many
# positions: a prefix hit, the lane's first row starts behind them on pages an earlier dispatch wrote).
LANE_ROWS = {
    "two_rows": dict(dispatches=[[(0, 16), (0, 13), None]]),
    "a_short_first_row": dict(dispatches=[[(0, 9), (0, 16), None]]),
    "three_rows": dict(dispatches=[[(0, 16), (0, 16), (0, 8), None]]),
    "two_lanes_whose_rows_straddle_the_groups": dict(at_once=2, dispatches=[
        [(0, 16), (0, 16), (0, 16), (0, 7), (1, 16), (1, 16), (1, 3), None]]),
    "a_padding_row_between_two_lanes": dict(dispatches=[[(0, 16), (0, 5), None, (1, 16), (1, 16), (1, 2)]]),
    "a_padding_row_between_two_lanes_in_groups": dict(at_once=2, dispatches=[
        [(0, 16), (0, 5), None, (1, 16), (1, 16), (1, 2)]]),
    "behind_a_prefix_hit_and_an_earlier_dispatch": dict(hits={1: (0, 32)}, dispatches=[
        [(0, 16), (0, 16), None, None, None, None], [(0, 16), (0, 9), (1, 16), (1, 5), None, None]]),
}


def check_lane_rows(mod, reference, shape, cfg, params, layout, monkeypatch):
    """Feeds ``LANE_ROWS[layout]`` through ``mod``'s ``forward_chunk`` and
    ``draft_chunk`` (every row with its lane's block table, ``lanes`` as the
    engine hands them) and holds every fed position's logits, and the
    prediction module's, against ``reference`` over each lane's WHOLE prompt;
    the program's own sums count every live row's read."""
    how = LANE_ROWS[layout]
    if "at_once" in how:  # models/xing4.py runs models/openpangu.py's `_in_groups`: one constant for both
        patched(monkeypatch, op, "TOKENS_AT_ONCE", how["at_once"] * C)
    fed = {}
    for d in how["dispatches"]:
        for lane, n in filter(None, d):
            fed[lane] = fed.get(lane, 0) + n
    hits = how.get("hits", {})
    starts = {lane: hits[lane][1] if lane in hits else 0 for lane in fed}  # a lane's first fed position
    prompts, tables, at = {}, {}, dict(starts)
    for lane, start in sorted(starts.items()):  # one token more than is fed: the last position's ``following``
        prompts[lane] = np.asarray(prompt_of(start + fed[lane] + 1, salt=11 + lane), np.int32)
        tables[lane] = np.arange(1 + MB * lane, 1 + MB * (lane + 1), dtype=np.int32)
        if start:  # the shared positions' tokens AND the token behind them (the module's last shared page)
            other = hits[lane][0]
            prompts[lane][:start + 1] = prompts[other][:start + 1]
            tables[lane][:start // BS] = tables[other][:start // BS]
    cache = mod.make_kv_cache(cfg, 1 + MB * len(fed), BS, drafting=True)
    got = {lane: ([], []) for lane in fed}

    chunk, draft = chunk_program(mod, cfg, raw=True), draft_program(mod, cfg)  # (eagerly the layouts take minutes)

    for d in how["dispatches"]:
        rows = len(d)
        toks, pos = np.zeros((rows, C), np.int32), np.full((rows, C), -1, np.int32)
        nxt, tabs = np.zeros((rows, C), np.int32), np.zeros((rows, MB), np.int32)
        lanes = np.full((rows,), len(fed), np.int32)  # a padding row is no lane's
        for r, row in enumerate(d):
            if row is None:
                continue
            lane, n = row
            a = at[lane]
            toks[r, :n], nxt[r, :n] = prompts[lane][a:a + n], prompts[lane][a + 1:a + n + 1]
            pos[r, :n], tabs[r], lanes[r] = np.arange(a, a + n), tables[lane], lane
            at[lane] += n
        x, cache, state, sums = chunk(params, *map(jnp.asarray, (toks, pos)), cache, jnp.asarray(tabs), None,
                                      jnp.asarray(lanes))
        assert state is None
        hd, cache, more = draft(params, x, *map(jnp.asarray, (nxt, pos)), cache, jnp.asarray(tabs))
        logits, drafts = mod.lm_head(params, cfg, mod.final_norm(params, cfg, x)), mod.lm_head(params, cfg, hd)
        for r, row in enumerate(d):
            if row is not None:
                got[row[0]][0].append(np.asarray(logits[r, :row[1]]))
                got[row[0]][1].append(np.asarray(drafts[r, :row[1]]))
        live = [int(pos[r].max()) + 1 for r, row in enumerate(d) if row is not None]
        counts, drafted = dict(zip(mod.COUNTERS, np.asarray(sums))), dict(zip(mod.COUNTERS, np.asarray(more)))
        # a live row attends ONE tile (this table is one) in every layer, its positions up to its last of it
        assert counts["mla_history_positions_read"] == cfg.num_layers * len(live) * MB * BS
        assert counts["mla_history_positions_live"] == cfg.num_layers * sum(live)
        assert drafted["mla_history_positions_live"] == sum(live) and drafted["mtp_layer_calls"] >= 1
    for lane, (logits, drafts) in got.items():
        tokens, span = jnp.asarray(prompts[lane]), jnp.arange(starts[lane], len(prompts[lane]) - 1)
        np.testing.assert_allclose(np.concatenate(logits),
                                   np.asarray(reference_program(reference, shape)(params, tokens, span)),
                                   atol=ATOL, err_msg=f"lane {lane}")
        np.testing.assert_allclose(np.concatenate(drafts),
                                   np.asarray(reference_program(reference, shape, "draft_logits")(params, tokens, span)),
                                   atol=ATOL, err_msg=f"lane {lane}: the prediction module")
