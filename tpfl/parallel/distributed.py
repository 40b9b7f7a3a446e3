"""Multi-process (cross-host) runtime for the federation engine.

One process per host, glued together by ``jax.distributed``: after
:func:`ensure_distributed` every participating process sees the SAME
global device list, so :func:`tpfl.parallel.engine.auto_mesh` can lay a
``hosts`` axis over the process grid and the engine's round program
runs as one SPMD program whose cross-host collectives ride DCN.

The CPU CI exercises this for real — ``jax_cpu_collectives_implementation
= "gloo"`` gives the host platform TCP collectives, and
``--xla_force_host_platform_device_count=K`` gives each worker K virtual
devices — so cross-host == single-process parity is machine-checked
without TPU hardware (tests/test_crosshost.py). On real pods the same entry point picks up the TPU runtime's
own coordinator (see docs/deployment.md).

Environment contract (the subprocess harness and real launchers both
use it): ``TPFL_COORDINATOR`` (host:port), ``TPFL_NUM_PROCESSES``,
``TPFL_PROCESS_ID``. Explicit arguments win over the environment.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np

__all__ = [
    "ensure_distributed",
    "is_multiprocess",
    "global_put",
    "local_data",
]

_initialized = False


def ensure_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    collectives: str = "gloo",
) -> bool:
    """Join the multi-process mesh if one is configured; idempotent.

    Resolution order per parameter: explicit argument, then the
    ``TPFL_COORDINATOR`` / ``TPFL_NUM_PROCESSES`` / ``TPFL_PROCESS_ID``
    environment, then "not configured". Returns True iff the process
    is part of a >1-process world after the call — a lone process (no
    coordinator configured, or a 1-process world) returns False and
    leaves JAX untouched, so single-host behavior is byte-identical.

    ``collectives`` selects the CPU host-platform collective backend
    ("gloo" is the one baked into jaxlib); accelerator backends bring
    their own and ignore it.
    """
    global _initialized
    if _initialized:
        return jax.process_count() > 1
    coordinator_address = coordinator_address or os.environ.get(
        "TPFL_COORDINATOR"
    )
    if num_processes is None:
        num_processes = int(os.environ.get("TPFL_NUM_PROCESSES", "0") or 0)
    if process_id is None:
        process_id = int(os.environ.get("TPFL_PROCESS_ID", "0") or 0)
    if not coordinator_address or int(num_processes) <= 1:
        return False
    jax.config.update("jax_cpu_collectives_implementation", collectives)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    _initialized = True
    return jax.process_count() > 1


def is_multiprocess() -> bool:
    """True when this process is one of several in a jax.distributed
    world — the condition under which global arrays stop being fully
    addressable and placement must go through :func:`global_put`."""
    return jax.process_count() > 1


def global_put(tree: Any, shardings: Any) -> Any:
    """Place a host pytree on the (possibly multi-host) mesh.

    ``shardings`` is either one ``jax.sharding.Sharding`` applied to
    every leaf or a matching pytree of them. Single-process: a plain
    ``jax.device_put`` — byte-identical to the historical path.
    Multi-process: every process holds the full host copy of the
    (small, already-replicated-by-construction) federation state, and
    each contributes exactly its addressable shards via
    ``jax.make_array_from_callback`` — the assembled global array
    spans the full mesh without any process touching remote shards.
    """
    single = isinstance(shardings, jax.sharding.Sharding)

    def put(leaf: Any, sh: Any) -> Any:
        if not is_multiprocess():
            return jax.device_put(leaf, sh)
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            # Already a global array — a chained window output. The
            # engine's out_shardings match its in_shardings by
            # construction, so no resharding collective is needed
            # (and np.asarray on it would raise).
            return leaf
        arr = np.asarray(leaf)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx, a=arr: a[idx]
        )

    if single:
        return jax.tree_util.tree_map(lambda l: put(l, shardings), tree)
    return jax.tree_util.tree_map(put, tree, shardings)


def local_data(x: Any) -> np.ndarray:
    """This process' first addressable shard of ``x`` as a NumPy array
    — the multi-process-safe way to digest a global array
    (``np.asarray`` on a non-fully-addressable jax.Array raises)."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return np.asarray(x.addressable_data(0))
    return np.asarray(x)
