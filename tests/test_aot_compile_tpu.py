"""The main path's Pallas decode kernels, compiled for the chip without the
chip: the TPU compiler installed beside JAX compiles for a *described*
``v5e:2x2`` (on-chip-measurement guide §2, third rehearsal). Interpret-mode
tests cannot see what it refuses — slices not aligned to the tiling, too much
scoped VMEM — so these few compiles guard every later PR at no chip time.
A compile that passes is not a chip run: ``python chip_smoke.py`` is.

Only one process may load libtpu, and it keeps it until exit: the topology
is described inside a module-scoped fixture (never at import, never in a
``skipif`` or ``parametrize`` argument), everything built from it is built in
fixtures or tests, and all such tests live in THIS file so one xdist worker
owns them.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dynamo_tpu.ops.attention import _v2_supported, decode_schedule
from dynamo_tpu.ops.pallas import paged_attention as pk

# the geometry the issue's table was asked at: 32 lanes, block 16,
# 24 blocks/lane, 1,024-block pool, bf16
S, BS, MB, N = 32, 16, 24, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4_mesh(topo):
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(tp=4), devices=topo.devices)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one would warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(h, kvh, d, sharding, kv_sharding=None, rep=None):
    def sd(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    kv_sharding = kv_sharding or sharding
    rep = rep or sharding
    return (
        sd((S, h, d), jnp.bfloat16, sharding),
        sd((N, BS, kvh, d), jnp.bfloat16, kv_sharding),
        sd((N, BS, kvh, d), jnp.bfloat16, kv_sharding),
        sd((S, MB), jnp.int32, rep),
        sd((S,), jnp.int32, rep),
    )


def _compiles_with_kernel(fn, args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("schedule", ["v1", "v2"])
def test_per_lane_schedules_compile_at_qwen15b_geometry(one_chip, schedule):
    fn = {"v1": pk.paged_attention_decode, "v2": pk.paged_attention_decode_v2}[schedule]
    assert decode_schedule(S, BS, 2, 128, 2, MB, sharded=True)[0] == "v2"
    assert _compiles_with_kernel(
        lambda *a: fn(*a, interpret=False, return_stats=True),
        _args(12, 2, 128, one_chip),
    )


def test_v4_compiles_at_llama1b_geometry(one_chip):
    name, plan = decode_schedule(S, BS, 8, 64, 2, MB)
    assert name == "v4"
    assert _compiles_with_kernel(
        lambda *a: pk.paged_attention_decode_v4(
            *a, pages_per_chunk=plan, interpret=False, return_stats=True
        ),
        _args(32, 8, 64, one_chip),
    )


def test_sharded_decode_with_one_kv_head_per_shard_compiles(tp4_mesh):
    """Regression: qwen2.5-7b at tp=4 (and 70B at tp=8) leaves ONE KV head
    per shard; the v2 schedule is refused there ("Slice shape along
    dimension 2 must be aligned to tiling (2), but is 1"), so the sharded
    wrapper must fall back to a schedule the compiler accepts."""
    assert not _v2_supported(128, 1)
    assert decode_schedule(S, BS, 1, 128, 2, MB, sharded=True)[0] == "v1"
    heads = NamedSharding(tp4_mesh, P(None, "tp", None))
    kv = NamedSharding(tp4_mesh, P(None, None, "tp", None))
    rep = NamedSharding(tp4_mesh, P())
    assert _compiles_with_kernel(
        lambda *a: pk.paged_attention_decode_sharded(
            *a, mesh=tp4_mesh, interpret=False, return_stats=True
        ),
        _args(28, 4, 128, heads, kv, rep),
    )


def test_v2_alignment_rule():
    """The rule's one home (ops/attention.py) — what the v5e compiler said
    when asked: D must fill the 128 lanes; bf16 KVH of 2, 4 or a multiple of
    8 compiles, 1/3/5/6/12 are refused; float32 has no KVH constraint."""
    assert not _v2_supported(64, 8)
    assert [k for k in (1, 2, 3, 4, 5, 6, 8, 12, 16, 24, 32)
            if _v2_supported(128, k)] == [2, 4, 8, 16, 24, 32]
    assert _v2_supported(128, 1, itemsize=4)
