"""Device mesh helpers.

The scaling recipe (jax-ml.github.io/scaling-book): pick a mesh,
annotate shardings, let XLA insert collectives. Axes used by tpfl:

- ``nodes`` — the federation axis: logical FL nodes sharded over chips
  (FederationEngine / VmapFederation). Collectives over it ride ICI.
- ``model`` — the model-parallel axis of the engine's 2D
  ``nodes x model`` mesh: each node's parameters/optimizer state are
  FSDP/TP-sharded over it per a :class:`SpecLayout` per-leaf
  PartitionSpec policy, so one node's model may exceed one chip's HBM
  while the federation still shards across ``nodes``. The fold's
  reduction stays over ``nodes`` only — every model shard folds its
  own slice.
- ``dp`` / ``fsdp`` / ``tp`` — batch / parameter sharding inside one
  standalone learner (ShardedTrainer).

Node counts that do not divide the mesh are PADDED, not replicated:
:func:`padded_node_count` rounds the node axis up to a multiple of the
mesh's NODE-axis size (never the model axis) and :func:`pad_node_axis`
/ :func:`pad_node_weights` fill the tail with clone rows at zero
FedAvg weight — the masked-mean fold already ignores w=0 entries
exactly, so padding changes no numerics while every device keeps an
equal shard. (Historically an indivisible node count silently fell
back to a replicated single-device placement, throwing away the
mesh.)
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

#: Canonical name of the federation axis.
NODE_AXIS = "nodes"

#: Canonical name of the model-parallel axis of the engine's 2D mesh.
MODEL_AXIS = "model"

#: Canonical name of the cross-host (multi-process / DCN) axis of the
#: engine's 3D ``hosts x nodes x model`` mesh. Collectives over it ride
#: DCN, not ICI — the engine folds per-host partial psums over
#: ``nodes`` first and only the partial aggregate crosses this axis.
HOST_AXIS = "hosts"

#: Axis-name aliases for standalone FSDP / tensor-parallel meshes
#: (ShardedTrainer / SpecLayout policies that split the two roles).
FSDP_AXIS = "fsdp"
TP_AXIS = "tp"


def device_report() -> dict[str, Any]:
    """The device triple every printed result names, as JAX reports it:
    ``{"platform", "kind", "count"}``."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_chip(min_count: int = 1) -> dict[str, Any]:
    """:func:`device_report` for a path that only means something on the
    accelerator (``chip_smoke.py``, ``benchmark/run.py``):
    raises instead of letting JAX's silent CPU fallback produce numbers
    under a device metric's name. A TPU kind missing from the peaks
    table is an error too — utilisation against an unknown peak is not
    a default, it is a guess."""
    from tpfl.management.profiling import PEAK_FLOPS

    report = device_report()
    if report["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX reports platform={report['platform']!r} "
            f"kind={report['kind']!r} — this path measures the chip and "
            "does not fall back to the CPU"
        )
    if report["kind"] not in PEAK_FLOPS:
        raise RuntimeError(
            f"device kind {report['kind']!r} is not in the peaks table "
            f"(tpfl.management.profiling.PEAK_FLOPS: {sorted(PEAK_FLOPS)})"
        )
    if report["count"] < min_count:
        raise RuntimeError(
            f"need {min_count} chips, JAX reports {report['count']}"
        )
    return report


def create_mesh(
    axes: Optional[dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh from an axis-name -> size dict.

    Defaults to one ``nodes`` axis over all local devices. Sizes must
    multiply to the device count; a single -1 size is inferred.
    """
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {NODE_AXIS: len(devices)})
    sizes = list(axes.values())
    if sizes.count(-1) == 1:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devices) // known
        axes = dict(zip(axes.keys(), sizes))
    total = int(np.prod(list(axes.values())))
    if total != len(devices):
        raise ValueError(
            f"Mesh axes {axes} need {total} devices, have {len(devices)}"
        )
    dev_array = np.asarray(devices).reshape(*axes.values())
    return Mesh(dev_array, tuple(axes.keys()))


def node_shard_dims(mesh: Optional[Mesh], axis: str = NODE_AXIS):
    """The mesh dims the stacked NODE axis shards over: ``(hosts,
    nodes)`` on a 3D multi-host mesh, ``(nodes,)`` otherwise. The
    leading stacked dimension always shards over ALL of them — each
    host's device shard holds a contiguous run of logical nodes."""
    if mesh is not None and mesh_axis_size(mesh, HOST_AXIS) > 1:
        return (HOST_AXIS, axis)
    return (axis,)


def node_shard_size(mesh: Optional[Mesh], axis: str = NODE_AXIS) -> int:
    """Combined size of the node-sharding dims (hosts x nodes on a 3D
    mesh) — the device multiple stacked node counts pad up to."""
    size = 1
    for a in node_shard_dims(mesh, axis):
        size *= mesh_axis_size(mesh, a)
    return size


def federation_sharding(mesh: Mesh, axis: str = NODE_AXIS) -> NamedSharding:
    """Sharding for node-stacked pytrees: leading axis over the mesh.

    The leading dimension must be a multiple of the mesh's combined
    node-shard size (:func:`node_shard_size` — ``hosts x nodes`` on a
    3D mesh); round indivisible node counts up with
    :func:`padded_node_count` + :func:`pad_node_axis` first (zero-weight
    pad rows are exact no-ops under the masked-mean fold)."""
    dims = node_shard_dims(mesh, axis)
    spec = PartitionSpec(dims if len(dims) > 1 else dims[0])
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def mesh_axis_size(mesh: Optional[Mesh], axis: str = NODE_AXIS) -> int:
    """Size of ``axis`` on ``mesh`` (1 for no mesh / missing axis)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get(axis, 1))


def padded_node_count(
    n_nodes: int, mesh: Optional[Mesh], axis: str = NODE_AXIS
) -> int:
    """``n_nodes`` rounded up to a multiple of the mesh's ``axis`` size
    — the stacked leading dimension that shards evenly. Equals
    ``n_nodes`` when there is no mesh or it already divides. 2D-aware
    by construction: only the named NODE axis' size enters — a
    ``nodes=4, model=2`` mesh pads to multiples of 4, never 8. On a 3D
    multi-host mesh the node axis shards over ``hosts x nodes``
    combined (:func:`node_shard_size`), so that product is the
    multiple."""
    d = node_shard_size(mesh, axis)
    return ((int(n_nodes) + d - 1) // d) * d


def capacity_tier(n_live: int, floor: int = 1) -> int:
    """Smallest power-of-two ≥ ``max(n_live, floor)`` — the elastic
    engine's capacity buckets. Programs compile at the TIER, not the
    live count, so membership churn inside a tier is a pure weight-mask
    edit (zero recompiles); only crossing a tier boundary re-lowers.
    Composes with :func:`padded_node_count`: the engine pads the tier
    up to a device multiple like any other node count."""
    n = max(int(n_live), int(floor), 1)
    tier = 1
    while tier < n:
        tier *= 2
    return tier


def pad_node_axis(tree: Any, n_padded: int) -> Any:
    """Pad every leaf's leading (node) axis to ``n_padded`` by cloning
    row 0 — pad rows must be VALID model/data rows (training them is
    well-defined), they are just excluded from the fold by their zero
    weight. No-op when already at ``n_padded``."""
    import jax.numpy as jnp

    def pad(leaf: Any) -> Any:
        leaf = jnp.asarray(leaf)
        extra = n_padded - leaf.shape[0]
        if extra <= 0:
            return leaf
        fill = jnp.broadcast_to(leaf[:1], (extra, *leaf.shape[1:]))
        return jnp.concatenate([leaf, fill], axis=0)

    return jax.tree_util.tree_map(pad, tree)


def pad_node_weights(weights: Any, n_padded: int) -> Any:
    """Pad a [N] (or per-round [R, N]) FedAvg weight vector with ZEROS
    on the node axis — the masked-mean fold ignores w=0 entries, so pad
    slots contribute nothing."""
    import jax.numpy as jnp

    w = jnp.asarray(weights, jnp.float32)
    extra = n_padded - w.shape[-1]
    if extra <= 0:
        return w
    pad_widths = [(0, 0)] * (w.ndim - 1) + [(0, extra)]
    return jnp.pad(w, pad_widths)


def valid_node_mask(n_nodes: int, n_padded: int) -> Any:
    """[n_padded] float mask: 1.0 for real nodes, 0.0 for pad rows —
    the uniform-fallback denominator when a round's weights are
    all-zero (uniform over REAL nodes, never over padding)."""
    import jax.numpy as jnp

    return (jnp.arange(n_padded) < n_nodes).astype(jnp.float32)


def shard_stacked(
    mesh: Optional[Mesh],
    tree: Any,
    n_nodes: Optional[int] = None,
    axis: str = NODE_AXIS,
) -> Any:
    """Place a node-stacked pytree on the mesh, padding the leading
    axis to a device multiple first (``n_nodes`` defaults to the first
    leaf's current leading size). With no mesh, returns the tree
    unchanged. On a 2D ``nodes x model`` mesh only the node axis is
    padded and sharded — leaves ride replicated over ``model`` (use
    :func:`stacked_model_shardings` for the per-leaf layout
    placement)."""
    if mesh is None:
        return tree
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return tree
    n = int(n_nodes if n_nodes is not None else np.shape(leaves[0])[0])
    tree = pad_node_axis(tree, padded_node_count(n, mesh, axis))
    return jax.device_put(tree, federation_sharding(mesh, axis))


# --- per-leaf model-axis PartitionSpec policy (SpecLayout) ----------------


@dataclass(frozen=True)
class SpecLayout:
    """Canonical per-leaf PartitionSpecs for the model axis.

    The 2D-mesh analogue of the fsdp/tp layout tables of large-model
    trainers (SNIPPETS [3]): a small ordered rule list mapping flax
    parameter PATHS (``TransformerBlock_0/Dense_2/kernel``) to the
    model-axis dims of the leaf's PartitionSpec. The engine prepends
    the ``nodes`` axis for node-stacked state, so a rule's dims
    describe ONE node's (unstacked) leaf.

    Rules are ``(path regex, dims)`` where ``dims`` is a tuple of
    ``MODEL_AXIS`` / None per leaf dimension; the first rule whose
    regex matches AND whose dims length equals the leaf's rank AND
    whose named dims divide the mesh's model-axis size wins.
    Unmatched leaves (and every leaf of the default empty layout) ride
    replicated on the model axis — the MLP/CNN zoo default, which
    keeps a 2D run numerically the plain data-parallel program."""

    name: str = "replicated"
    rules: tuple = ()
    model_axis: str = MODEL_AXIS

    def leaf_dims(
        self, path: str, shape: Sequence[int], axis_size: int
    ) -> tuple:
        """Model-axis dims for one unstacked leaf at ``path`` (see
        class docs); ``(None, ...)`` = replicated on the model axis."""
        ndim = len(shape)
        if axis_size > 1:
            for pattern, dims in self.rules:
                if len(dims) != ndim or not re.search(pattern, path):
                    continue
                if all(
                    d is None or shape[i] % axis_size == 0
                    for i, d in enumerate(dims)
                ):
                    return tuple(dims)
        return (None,) * ndim

    def leaf_spec(
        self, path: str, shape: Sequence[int], axis_size: int
    ) -> PartitionSpec:
        """The unstacked leaf's PartitionSpec (model-axis dims only)."""
        return PartitionSpec(*self.leaf_dims(path, shape, axis_size))


def transformer_layout() -> SpecLayout:
    """The TransformerLM layout: embeddings sharded over their row
    (vocab / position) dim FSDP-style; QKV and FFN-up kernels
    column-parallel (out-features on ``model``), attention-out and
    FFN-down kernels row-parallel (in-features on ``model``) — the
    Megatron pairing, so the block's collectives stay one reduce per
    matmul pair; the logits head column-parallel over the vocab.
    Biases of column-parallel kernels shard with their out-features;
    LayerNorm scales/biases and everything else ride replicated."""
    m = MODEL_AXIS
    return SpecLayout(
        name="transformer",
        rules=(
            (r"embedding$", (m, None)),
            (r"TransformerBlock_\d+/Dense_[02]/kernel$", (None, m)),
            (r"TransformerBlock_\d+/Dense_[13]/kernel$", (m, None)),
            (r"TransformerBlock_\d+/Dense_[02]/bias$", (m,)),
            (r"^Dense_\d+/kernel$", (None, m)),
            (r"^Dense_\d+/bias$", (m,)),
        ),
    )


#: Named layouts ``Settings.SHARD_LAYOUT`` / engine ``layout=`` select.
LAYOUTS = {
    "replicated": SpecLayout,
    "transformer": transformer_layout,
}


def layout_for_module(module: Any, policy: str = "auto") -> SpecLayout:
    """Resolve the per-leaf model-axis layout for a zoo module.

    ``policy`` is a layout name from :data:`LAYOUTS`, or ``"auto"``:
    the module's own ``spec_layout`` attribute (the zoo's transformer
    declares ``"transformer"``), falling back to ``"replicated"`` —
    MLP/CNN/ResNet leaves ride replicated on the model axis by
    default."""
    if policy == "auto":
        policy = getattr(module, "spec_layout", "replicated") or "replicated"
    factory = LAYOUTS.get(policy)
    if factory is None:
        raise ValueError(
            f"unknown model-axis layout {policy!r}; have "
            f"{sorted(LAYOUTS)} (or 'auto')"
        )
    return factory()


def _path_str(path: tuple) -> str:
    """``TransformerBlock_0/Dense_1/kernel`` from a tree_map_with_path
    key path (flax DictKeys / GetAttrKeys / sequence indices)."""
    parts = []
    for k in path:
        for attr in ("key", "name", "idx"):
            v = getattr(k, attr, None)
            if v is not None:
                parts.append(str(v))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def stacked_model_shardings(
    mesh: Mesh, tree: Any, layout: SpecLayout
) -> Any:
    """Per-leaf NamedShardings for a NODE-STACKED state tree on a 2D
    mesh: ``P(nodes, *layout dims)`` — the leading node axis shards
    over ``nodes`` (``(hosts, nodes)`` on a 3D multi-host mesh), each
    node's model over ``model`` per the layout."""
    axis_size = mesh_axis_size(mesh, layout.model_axis)
    lead_dims = node_shard_dims(mesh)
    lead = lead_dims if len(lead_dims) > 1 else lead_dims[0]

    def one(path, leaf):
        shape = tuple(np.shape(leaf))[1:]
        dims = layout.leaf_dims(_path_str(path), shape, axis_size)
        return NamedSharding(mesh, PartitionSpec(lead, *dims))

    return jax.tree_util.tree_map_with_path(one, tree)


def global_model_shardings(mesh: Mesh, tree: Any, layout: SpecLayout) -> Any:
    """Per-leaf NamedShardings for an UNSTACKED (global, node-
    replicated) model tree — SCAFFOLD's ``c_global``: replicated over
    ``nodes``, sharded over ``model`` per the layout."""
    axis_size = mesh_axis_size(mesh, layout.model_axis)

    def one(path, leaf):
        shape = tuple(np.shape(leaf))
        return NamedSharding(
            mesh, layout.leaf_spec(_path_str(path), shape, axis_size)
        )

    return jax.tree_util.tree_map_with_path(one, tree)
