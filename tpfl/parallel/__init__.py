"""TPU execution layer: meshes, vmapped federations, sharded training.

This is the green-field value-add over the reference (SURVEY §2.10): the
reference's only intra-host parallelism is a Ray actor pool multiplexing
N learner *processes* over K CPUs (``actor_pool.py:69``), with weights
round-tripping through pickle on every hop. Here:

- :class:`FederationEngine` — the pod-scale seam (tpfl.parallel.engine):
  an ENTIRE federation round (per-node local train, gossip exchange,
  streaming FedAvg/SCAFFOLD/FedProx fold) compiled to one sharded XLA
  program over the mesh, gossip realized as ``lax.psum`` collectives on
  the ``nodes`` axis, node counts padded to device multiples with
  zero-weight rows, and multi-round ``lax.fori_loop`` windows that pay
  the host dispatch RTT once per window (docs/scaling.md).
- :class:`VmapFederation` — the stable high-level API over the engine:
  N homogeneous FL nodes stacked on a leading node axis; every node's
  local epoch runs inside ONE compiled XLA program (vmap over
  lax.scan), the node axis is sharded over the device mesh, and FedAvg
  is an exact on-device weighted reduction instead of
  gossip-until-converged.
- :func:`create_mesh` / :func:`federation_sharding` — mesh + sharding
  helpers for single-host (8-chip) and multi-host topologies; 2D
  ``nodes x model`` meshes shard each node's model over chips per a
  :class:`SpecLayout` per-leaf PartitionSpec policy
  (``SHARD_MODEL``/``SHARD_LAYOUT``), federating models bigger than
  one chip's HBM (docs/scaling.md "2D mesh").
- :class:`ShardedTrainer` — data-parallel + FSDP sharding for one large
  model across the mesh (tpfl.parallel.sharded).
"""

from tpfl.parallel.mesh import (
    HOST_AXIS,
    MODEL_AXIS,
    NODE_AXIS,
    SpecLayout,
    create_mesh,
    device_report,
    federation_sharding,
    global_model_shardings,
    layout_for_module,
    pad_node_axis,
    pad_node_weights,
    padded_node_count,
    replicated,
    require_chip,
    shard_stacked,
    stacked_model_shardings,
    transformer_layout,
)
from tpfl.parallel.engine import (
    EngineWindow,
    FederationEngine,
    FedBuffSchedule,
    resolve_shard_hosts,
    sample_participants,
)
from tpfl.parallel.distributed import (
    ensure_distributed,
    global_put,
    is_multiprocess,
    local_data,
)
from tpfl.parallel.population import ClientPopulation
from tpfl.parallel.federation import VmapFederation
from tpfl.parallel.federation_learner import FederationLearner
from tpfl.parallel.window_pipeline import WindowPipeline, WindowPrefetcher
from tpfl.parallel.moe import make_moe_layer, moe_dispatch
from tpfl.parallel.pipeline import make_pipeline, pipeline_forward
from tpfl.parallel.ring_attention import (
    blockwise_attention,
    make_ring_attention,
    ring_attention,
)
from tpfl.parallel.sharded import ShardedTrainer


def __getattr__(name):
    # Lazy: flash_attention pulls jax.experimental.pallas (~1s import),
    # a serving-only fast path most tpfl.parallel users never touch.
    if name == "flash_attention":
        from tpfl.parallel.flash_kernel import flash_attention

        return flash_attention
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))

__all__ = [
    "create_mesh",
    "device_report",
    "require_chip",
    "federation_sharding",
    "replicated",
    "padded_node_count",
    "pad_node_axis",
    "pad_node_weights",
    "shard_stacked",
    "HOST_AXIS",
    "MODEL_AXIS",
    "NODE_AXIS",
    "SpecLayout",
    "layout_for_module",
    "transformer_layout",
    "stacked_model_shardings",
    "global_model_shardings",
    "FederationEngine",
    "EngineWindow",
    "FedBuffSchedule",
    "ClientPopulation",
    "ensure_distributed",
    "is_multiprocess",
    "global_put",
    "local_data",
    "resolve_shard_hosts",
    "WindowPipeline",
    "WindowPrefetcher",
    "sample_participants",
    "VmapFederation",
    "FederationLearner",
    "ShardedTrainer",
    "flash_attention",
    "blockwise_attention",
    "ring_attention",
    "make_ring_attention",
    "make_pipeline",
    "make_moe_layer",
    "moe_dispatch",
    "pipeline_forward",
]
