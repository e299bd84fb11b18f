"""Device mesh construction and logical→physical sharding rules.

Axes (superset of the reference's capability; reference delegates TP/PP to
engines, SURVEY.md §2.12 — here they are native):

- ``dp``: data parallel — batch-slot axis of the continuous batcher
- ``pp``: pipeline parallel — layer-stage axis (parallel/pipeline.py runs
  GPipe-style microbatching over it with shard_map + ppermute)
- ``tp``: tensor parallel — attention heads / MLP intermediate
- ``sp``: sequence/context parallel — ring-attention axis for long context
  (parallel/ring_attention.py; a TPU-native extension — the reference has
  none, SURVEY.md §2.12)
- ``ep``: expert parallel — the expert axis of ``ops/moe.py:moe_mlp``, the
  GShard-style layer with a fixed capacity (``models/llama.py``'s Mixtral
  form; the reference has no EP either, SURVEY.md §2.12). A caller that
  builds a ``MeshConfig`` itself sizes it; ``build_jax_serving_engine`` sizes
  NO ``ep`` (its flags are tp, pp, sp and dp), so on the served path the rule
  ``experts`` below resolves to "replicated". The DROPLESS layer
  (``ops/moe.py:dropless_experts``) does not go through these rules: a module
  that serves it on a mesh lays its experts over the mesh's one axis larger
  than 1 (:func:`model_axis`: ``--tensor-parallel-size 4`` gives ``tp``), the
  axis its heads lie over, and sums the shards' parts itself
  (``models/mellum.py``). An axis of experts beside a data-parallel attention
  (the all-to-all form) is ROADMAP M1's remainder.

The design follows the standard JAX recipe: pick a mesh, annotate shardings
with PartitionSpec, let XLA insert the collectives over ICI.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Total size must equal the number of devices used."""

    dp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.tp * self.sp * self.ep

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return (AXIS_DP, AXIS_PP, AXIS_SP, AXIS_EP, AXIS_TP)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.pp, self.sp, self.ep, self.tp)


def make_mesh(config: MeshConfig, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with dp as the outermost (slowest) axis and tp innermost.

    tp is innermost so tensor-parallel collectives (the most latency-sensitive)
    ride adjacent ICI links; dp crosses the slowest links.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < config.size:
        raise ValueError(f"mesh needs {config.size} devices, have {len(devs)}")
    grid = np.asarray(devs[: config.size]).reshape(config.shape)
    return Mesh(grid, config.axis_names)


# -- logical sharding rules --------------------------------------------------
# Model code annotates arrays with *logical* axis names; this table maps them
# to mesh axes. Unlisted logical axes are replicated.

_LOGICAL_RULES = {
    "batch": AXIS_DP,
    "seq": AXIS_SP,
    "layers": AXIS_PP,  # stacked layer axis → pipeline stages
    "heads": AXIS_TP,  # attention query heads
    "kv_heads": AXIS_TP,  # attention kv heads (GQA)
    "mlp": AXIS_TP,  # MLP intermediate dim
    "vocab": AXIS_TP,  # embedding/unembedding vocab dim
    "experts": AXIS_EP,  # ops/moe.py:moe_mlp's expert axis; no CLI flag sizes ep (header)
    "embed": None,  # model dim: replicated (Megatron-style TP)
    "kv_blocks": None,  # paged-KV physical block axis: replicated across tp
}


def logical_to_sharding(mesh: Mesh, *logical_axes: Optional[str]) -> NamedSharding:
    """Map a tuple of logical axis names (or None) to a NamedSharding."""
    spec = []
    for ax in logical_axes:
        if ax is None:
            spec.append(None)
            continue
        if ax not in _LOGICAL_RULES:
            raise KeyError(f"unknown logical axis {ax!r}")
        mesh_ax = _LOGICAL_RULES[ax]
        # Don't shard over an axis the mesh doesn't have (or of size 1).
        if mesh_ax is not None and mesh_ax in mesh.axis_names and mesh.shape[mesh_ax] > 1:
            spec.append(mesh_ax)
        else:
            spec.append(None)
    return NamedSharding(mesh, P(*spec))


def model_axis(mesh: Mesh) -> Tuple[str, int]:
    """(name, size) of the mesh's ONE axis larger than 1: what a module that
    writes its own collectives lays its heads, experts and vocabulary over
    (``models/mellum.py``). A mesh with several is refused by name: such a
    module's programs are one ``shard_map`` over one axis."""
    large = [(name, size) for name, size in mesh.shape.items() if size > 1]
    if len(large) != 1:
        raise ValueError(
            f"a mesh of {dict(mesh.shape)}: a module with its own programs serves over ONE "
            f"mesh axis larger than 1 (--tensor-parallel-size alone)")
    return large[0]


def kv_cache_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for paged KV cache [layers, blocks, block_size, kv_heads, head_dim]:
    layers over pp (each pipeline stage owns its layers' pages), kv heads
    over tp, physical blocks replicated across dp.

    Replication over dp is deliberate, not an oversight: the pod scaling
    story for KV capacity is WORKER REPLICAS behind KV-aware routing —
    each replica owns its whole pool and its own failure domain — exactly
    the reference's data-parallel model (SURVEY.md §2.12: multiple workers
    on one endpoint + router). The in-engine dp axis exists to batch slots
    across chips inside one worker; giving dp groups disjoint pools would
    re-create the router's placement problem inside the engine for no
    capacity win over replicas."""
    return logical_to_sharding(mesh, "layers", "kv_blocks", None, "kv_heads", None)
