"""Expert-parallel MoE layer: routing parity, capacity semantics, and
execution over an ep mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.moe import (
    MoeConfig,
    init_moe_params,
    moe_mlp,
    moe_mlp_reference,
    moe_param_logical_axes,
)
from dynamo_tpu.parallel.mesh import MeshConfig, logical_to_sharding, make_mesh

CFG = MoeConfig(hidden_size=32, intermediate_size=64, num_experts=4, top_k=2,
                capacity_factor=8.0)  # capacity ample: nothing drops


@pytest.fixture(scope="module")
def params():
    return init_moe_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def test_matches_dense_reference(params):
    """With ample capacity the dispatch/combine einsum path must equal the
    exact per-token top-k mixture."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, CFG.hidden_size), jnp.float32)
    got, aux = moe_mlp(params, CFG, x)
    want = moe_mlp_reference(params, CFG, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(aux["dropped_fraction"]) == 0.0
    assert float(aux["load_balancing_loss"]) > 0.0


def test_capacity_overflow_drops_gracefully(params):
    """A tiny capacity drops overflow tokens (their expert contribution is
    zero) without corrupting other tokens."""
    import dataclasses

    tight = dataclasses.replace(CFG, capacity_factor=0.25)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, CFG.hidden_size), jnp.float32)
    got, aux = moe_mlp(params, tight, x)
    assert np.isfinite(np.asarray(got)).all()
    assert float(aux["dropped_fraction"]) > 0.0


def test_runs_on_ep_mesh_with_parity(params):
    """Experts sharded over ep=2 (with tp=2 composing) produce the same
    numbers as the unsharded layer — GSPMD inserts the all-to-alls."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, CFG.hidden_size), jnp.float32)
    want, _ = moe_mlp(params, CFG, x)

    for mesh_cfg in (MeshConfig(ep=2), MeshConfig(ep=2, tp=2)):
        mesh = make_mesh(mesh_cfg)
        axes = moe_param_logical_axes()
        sharded = {
            k: jax.device_put(v, logical_to_sharding(mesh, *axes[k]))
            for k, v in params.items()
        }
        got, _ = jax.jit(lambda p, x_: moe_mlp(p, CFG, x_))(sharded, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5,
            err_msg=f"mesh {mesh_cfg}",
        )


def test_router_determinism_and_noise(params):
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, CFG.hidden_size), jnp.float32)
    a, _ = moe_mlp(params, CFG, x)
    b, _ = moe_mlp(params, CFG, x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c, _ = moe_mlp(params, CFG, x, router_noise_key=jax.random.PRNGKey(7))
    assert np.isfinite(np.asarray(c)).all()


def test_moe_family_serves_with_engine_parity(run):
    """The mixtral-style MoE family (tiny-moe preset) SERVES through the
    full engine: greedy outputs agree between single-step and multi-step
    decode configs, and an ep=2 x tp=2 mesh serves the same tokens as the
    unsharded engine."""
    import dataclasses

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params, param_shardings
    from dynamo_tpu.runtime.engine import Context

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny-moe"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [list(range(3, 19)), list(range(30, 38))]

    async def collect(engine, prompt):
        req = PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=5, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for item in engine.generate(Context(req)):
            assert not item.is_error, item.error_message()
            toks.extend((item.data or {}).get("token_ids", []))
        return toks

    def serve_all(engine):
        async def go():
            return [await collect(engine, p) for p in prompts]

        out = run(go())
        engine.close()
        return out

    base_cfg = EngineConfig(max_slots=2, kv_block_size=8, max_model_len=64)
    golden = serve_all(JaxServingEngine(cfg, params, base_cfg, cache_dtype=jnp.float32))
    assert all(len(t) == 5 for t in golden)

    multi = serve_all(JaxServingEngine(
        cfg, params,
        dataclasses.replace(base_cfg, decode_steps=4),
        cache_dtype=jnp.float32,
    ))
    assert multi == golden

    mesh = make_mesh(MeshConfig(ep=2, tp=2))
    sharded = jax.device_put(params, param_shardings(cfg, mesh))
    on_mesh = serve_all(JaxServingEngine(
        cfg, sharded, base_cfg, mesh=mesh, cache_dtype=jnp.float32,
    ))
    assert on_mesh == golden, f"ep2xtp2 serving diverged: {on_mesh} vs {golden}"


def test_padding_tokens_cannot_steal_expert_capacity(params):
    """A mostly-padded batch (the serving engine's static shapes) must give
    the real tokens EXACTLY their unpadded outputs: padding rows all route
    identically and would otherwise fill expert capacity ahead of real
    tokens (review finding: max abs err 0.93 on the live token)."""
    import dataclasses

    tight = dataclasses.replace(CFG, capacity_factor=1.0)
    real = jax.random.normal(jax.random.PRNGKey(9), (1, 4, CFG.hidden_size), jnp.float32)
    want = moe_mlp_reference(params, tight, real)

    padded = jnp.zeros((16, 4, CFG.hidden_size), jnp.float32).at[0].set(real[0])
    valid = jnp.zeros((16, 4), bool).at[0].set(True)
    got, aux = moe_mlp(params, tight, padded, token_valid=valid)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), atol=1e-5,
        err_msg="real token corrupted by padding routing",
    )
    assert float(aux["dropped_fraction"]) == 0.0
    # padding rows contribute nothing
    np.testing.assert_array_equal(np.asarray(got[1:]), 0.0)


def test_moe_int8_expert_quantization(params):
    """int8 expert weights (VERDICT r4 item 2): _expert_mat dequantizes per
    (expert, out-channel); the quantized MoE output must track the bf16 one
    within the absmax/127 reconstruction error, with identical routing."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, CFG.hidden_size), jnp.float32)

    def quant(w):
        wf = np.asarray(w, np.float32)
        s = np.maximum(np.abs(wf).max(axis=-2) / 127.0, 1e-12)
        q = np.clip(np.round(wf / s[..., None, :]), -127, 127).astype(np.int8)
        return {"q": jnp.asarray(q), "s": jnp.asarray(s)}

    qp = dict(params)
    for name in ("w_gate", "w_up", "w_down"):
        qp[name] = quant(params[name])

    got, _ = moe_mlp(qp, CFG, x)
    want, _ = moe_mlp(params, CFG, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.05)


def test_llama_moe_int8_family_quantizes():
    """quantize_params_int8 covers the MoE family (the r4 guard is gone):
    expert stacks [L, X, in, out] quantize over the in axis, the router
    stays float, and the quantized forward runs."""
    import dataclasses as _dc

    from dynamo_tpu.models.llama import (
        LLAMA_PRESETS,
        forward,
        init_params,
        make_kv_cache,
        quantize_params_int8,
        quantized_logical_axes,
    )

    cfg = _dc.replace(LLAMA_PRESETS["tiny-moe"], dtype=jnp.float32)
    p = init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params_int8(p, cfg)
    wg = qp["layers"]["w_gate"]
    assert wg["q"].dtype == jnp.int8
    assert wg["q"].shape == p["layers"]["w_gate"].shape
    assert wg["s"].shape == p["layers"]["w_gate"].shape[:2] + (
        p["layers"]["w_gate"].shape[-1],
    )
    assert not isinstance(qp["layers"]["moe_router"], dict)  # router unquantized
    # logical axes for scales drop the contracted axis, keep experts/mlp
    ax = quantized_logical_axes(cfg)["layers"]["w_gate"]
    assert ax["s"] == ("layers", "experts", "mlp")

    cache = make_kv_cache(cfg, 8, 8, dtype=jnp.float32)
    tokens = jnp.asarray([[5, 3, 7, 1]], jnp.int32)
    positions = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    tables = jnp.asarray([[0, 1]], jnp.int32)
    logits, _ = forward(qp, cfg, tokens, positions, cache, tables)
    ref, _ = forward(p, cfg, tokens, positions, cache, tables)
    assert not np.isnan(np.asarray(logits)).any()
    # same argmax as the unquantized model on a tiny model
    assert (np.asarray(logits[0, -1]).argmax() == np.asarray(ref[0, -1]).argmax())


# -- the softmax router (ops/moe.py:route_softmax_topk; models/qwen3_next.py) ---

@pytest.mark.parametrize("case", ["renormalised", "not_renormalised", "ties", "float32_at_the_highest_precision"])
def test_route_softmax_topk(case):
    """Softmax over ALL the logits, then the ``top_k`` largest: a chosen expert
    weighs its probability over the sum of the chosen ones (``renormalize``) or
    its probability itself; of equal probabilities the lower id wins, whatever
    the order they come in; and the logits are a float32 product at the highest
    precision whatever precision is in force (on the MXU a default-precision
    float32 product rounds its operands to bfloat16: a near-tie then decides
    by the rounding)."""
    import numpy as np

    from dynamo_tpu.ops.moe import route_softmax_topk

    x = jax.random.normal(jax.random.PRNGKey(0), (7, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    probs = np.asarray(jax.nn.softmax(jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST), axis=-1), np.float64)
    order = np.argsort(-probs, axis=-1, kind="stable")[:, :4]
    if case in ("renormalised", "not_renormalised"):
        ids, weights = route_softmax_topk(x, router, 4, case == "renormalised")
        chosen = np.take_along_axis(probs, order, axis=-1)
        want = chosen / chosen.sum(-1, keepdims=True) if case == "renormalised" else chosen
        np.testing.assert_array_equal(np.asarray(ids), order)
        np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-6)
        assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
        if case == "renormalised":
            np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
            # ... which IS the softmax over the chosen logits alone
            logits = np.take_along_axis(np.asarray(jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)), order, -1)
            np.testing.assert_allclose(np.asarray(weights), np.asarray(jax.nn.softmax(logits, axis=-1)), rtol=1e-5)
        else:
            assert float(np.asarray(weights).sum(-1).max()) < 1.0
    elif case == "ties":
        # experts 3, 9 and 12 score alike and highest, 5 next: top-2 takes the two lowest ids of the tie
        tied = jnp.zeros((32, 16)).at[0, jnp.asarray([3, 9, 12])].set(2.0).at[0, 5].set(1.0)
        one = jnp.zeros((1, 32)).at[0, 0].set(1.0)
        ids, weights = route_softmax_topk(one, tied, 2, True)
        assert np.asarray(ids).tolist() == [[3, 9]] and np.asarray(weights).tolist() == [[0.5, 0.5]]
        ids, _ = route_softmax_topk(one, tied, 4, True)
        assert np.asarray(ids).tolist() == [[3, 9, 12, 5]]
    else:
        # activations that bfloat16 cannot hold: a rounded product would move the probabilities by 1e-3
        fine = x * (1.0 + 2.0 ** -10)
        with jax.default_matmul_precision("bfloat16"):
            _, low = route_softmax_topk(fine, router, 4, False)
        with jax.default_matmul_precision("highest"):
            _, high = route_softmax_topk(fine, router, 4, False)
        np.testing.assert_array_equal(np.asarray(low), np.asarray(high))
        _, from_bf16 = route_softmax_topk(fine.astype(jnp.bfloat16), router, 4, False)  # widened, not re-rounded
        assert from_bf16.dtype == jnp.float32
