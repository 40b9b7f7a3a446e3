"""Cross-host parity harness: multi-process engine runs on CPU CI.

The 3D engine's acceptance bar (ISSUE 18) is machine-checked parity:
a 2-process ``jax.distributed`` run of the SAME logical federation
must land allclose to the single-process run. This module is both
sides of that check:

- :func:`demo_run` — the shared payload: a small seeded MLP
  federation driven through :class:`~tpfl.parallel.engine
  .FederationEngine` on whatever mesh ``auto_mesh()`` resolves under
  the current ``SHARD_*`` knobs. Every process computes the same
  host-side inputs (seeded numpy), so the run is reproducible across
  any process topology; the result is the folded global model (row 0
  of the unpadded stack), the last round's per-node losses, and a
  byte digest of the full stack for same-topology determinism checks.
- :func:`worker_main` — the subprocess entry point
  (``python -m tpfl.parallel.crosshost``): joins the world via
  :func:`~tpfl.parallel.distributed.ensure_distributed` (the
  ``TPFL_COORDINATOR``/``TPFL_NUM_PROCESSES``/``TPFL_PROCESS_ID`` env
  contract), applies the knob overrides from ``TPFL_CROSSHOST_CFG``,
  runs :func:`demo_run`, and writes its JSON result to
  ``<TPFL_CROSSHOST_OUT>.<process_id>.json``.
- :func:`launch` — the orchestrator tests call in-process: forks
  N workers with per-process env (``JAX_PLATFORMS=cpu`` and
  ``--xla_force_host_platform_device_count=K`` BEFORE the child
  imports jax — the reason this is a subprocess harness at all),
  waits, and returns their parsed results.

No TPU required anywhere: CPU collectives ride gloo (see
tpfl/parallel/distributed.py). On a real pod the same ``demo_run``
executes under the TPU runtime's own coordinator.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
from typing import Any, Optional

import numpy as np

__all__ = ["demo_run", "launch", "worker_main", "free_port"]

#: Knobs a harness config may override in the worker before the run —
#: a closed set so a config file cannot reach arbitrary settings.
_KNOBS = (
    "SHARD_NODES",
    "SHARD_DEVICES",
    "SHARD_MODEL",
    "SHARD_HOSTS",
    "ENGINE_WIRE_CODEC",
    "WIRE_TOPK_FRAC",
    "ENGINE_TELEMETRY",
    "ENGINE_DONATE",
    "RANK_CONTRACTS",
)


def free_port() -> int:
    """An OS-assigned free TCP port for the coordinator."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _apply_knobs(knobs: Optional[dict]) -> None:
    from tpfl.settings import Settings

    for name, value in (knobs or {}).items():
        if name not in _KNOBS:
            raise ValueError(f"crosshost config knob {name!r} not allowed")
        setattr(Settings, name, value)


def demo_run(
    nodes: int = 8,
    rounds: int = 2,
    seed: int = 0,
    algorithm: str = "fedavg",
    fork_rank: Optional[int] = None,
) -> dict:
    """One deterministic engine federation under the current knobs.

    Same ``(nodes, rounds, seed, algorithm)`` ⇒ the same logical run on
    ANY topology — 1 process × 8 devices, 2 × 4, forced
    ``SHARD_HOSTS`` — so results from different worlds are directly
    comparable (allclose across topologies; byte-equal within one).

    ``fork_rank`` is the divergence-proof harness: that rank (and only
    it) dispatches one extra rank-LOCAL program after the shared run,
    so its ``RANK_CONTRACTS`` receipt forks from the fleet's and
    :func:`launch`'s cross-rank comparison must fail with a (rank,
    ordinal, key) witness — the negative control proving the receipts
    actually detect divergence.
    """
    import jax

    from tpfl.models import MLP
    from tpfl.parallel import ranksafe
    from tpfl.parallel.engine import FederationEngine, auto_mesh
    from tpfl.parallel.mesh import mesh_axis_size, replicated, HOST_AXIS

    # One receipt per run: dispatches recorded before this harness
    # entered (in-process callers) must not ride this run's receipt.
    ranksafe.clear()

    rng = np.random.default_rng(seed)
    xs = rng.random((nodes, 1, 8, 8, 8), np.float32)
    ys = rng.integers(0, 10, (nodes, 1, 8)).astype(np.int32)
    w = np.ones((nodes,), np.float32)
    w[:: max(nodes // 2, 1)] = 0.0  # partial participation, seeded shape
    if not w.any():
        w[:] = 1.0

    mesh = auto_mesh()
    eng = FederationEngine(
        MLP(hidden_sizes=(8,)), nodes, mesh=mesh, seed=seed,
        algorithm=algorithm, learning_rate=0.1,
    )
    p = eng.init_params((8, 8))
    dx, dy = eng.shard_data(xs, ys)
    p, losses = eng.run_rounds(
        p, dx, dy, weights=w, n_rounds=rounds, donate=False
    )

    # rank-dependent: deliberate divergence harness — the probe engine
    # is mesh=None (rank-local, no collectives, cannot hang the world);
    # its extra dispatch forks THIS rank's receipt so launch()'s
    # cross-rank comparison must fail with a named witness.
    if fork_rank is not None and jax.process_index() == int(fork_rank):
        probe = FederationEngine(
            MLP(hidden_sizes=(8,)), 2, mesh=None, seed=seed,
            algorithm=algorithm, learning_rate=0.1,
        )
        probe.run_rounds(
            probe.init_params((8, 8)),
            *probe.shard_data(xs[:2], ys[:2]),
            n_rounds=1, donate=False,
        )

    def fetch(x: Any) -> np.ndarray:
        # Multi-process outputs are global (not fully addressable):
        # all-gather through an identity jit onto the replicated
        # sharding, then read the local copy.
        if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
            x = jax.jit(lambda a: a, out_shardings=replicated(eng.mesh))(x)
            x = x.addressable_data(0)
        return np.asarray(x)

    stack = jax.tree_util.tree_map(fetch, eng.unpad(p))
    leaves = jax.tree_util.tree_leaves(stack)
    global_row = np.concatenate(
        [leaf[0].astype(np.float64).ravel() for leaf in leaves]
    )
    import hashlib

    from tpfl.learning.serialization import leaf_bytes

    h = hashlib.sha256()
    for leaf in leaves:
        h.update(leaf_bytes(leaf))
    digest = h.hexdigest()
    # The cross-host receipt: bytes the DCN leg ships per round under
    # the active codec — hosts × codec'd-model bytes, the exact
    # constant the telemetry carry's dcn_bytes row records
    # (tests/test_crosshost.py pins carry == constant and the
    # dense/quant8 ratio of it).
    from tpfl.learning import compression

    # The fleet-observatory leg (ISSUE-20): every worker receipt
    # embeds a one-shot snapshot of its process registry, restricted
    # to the deterministic series (tpfl_engine_* / tpfl_pop_* /
    # tpfl_slo_*) so rank-0's fold — fleetobs.fold_receipts — renders
    # byte-identically across same-seed runs. origin = the jax
    # process index, the label the merged view keys per-rank series
    # by. The cross-host window's telemetry rows are globally sharded
    # (engine_obs.replay_window skips them — the observatory fan-out
    # is a single-host plane), so under ENGINE_TELEMETRY each worker
    # emits its per-rank engine series HERE, as pure functions of the
    # deterministic run outputs.
    from tpfl.management import fleetobs
    from tpfl.management.telemetry import metrics
    from tpfl.settings import Settings

    if Settings.ENGINE_TELEMETRY:
        rank_labels = {"node": f"rank{jax.process_index()}"}
        metrics.counter(
            "tpfl_engine_rounds_total", float(rounds), labels=rank_labels
        )
        metrics.gauge(
            "tpfl_engine_loss",
            float(np.mean(fetch(losses)[:nodes])),
            labels=rank_labels,
        )
        metrics.gauge(
            "tpfl_engine_model_norm",
            float(np.linalg.norm(global_row)),
            labels=rank_labels,
        )
    metrics_snapshot = fleetobs.snapshot(
        origin=str(jax.process_index()),
        prefixes=fleetobs.DETERMINISTIC_PREFIXES,
    )

    hosts = mesh_axis_size(mesh, HOST_AXIS) if mesh is not None else 1
    dcn_bytes = 0
    if hosts > 1:
        _, bits, frac = eng._resolve_variant()
        dcn_bytes = hosts * compression.wire_bytes_per_model(
            jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), p
            ),
            bits,
            frac,
        )
    return {
        "loss_mean": float(np.mean(fetch(losses)[:nodes])),
        # Ordered (cache key, HLO fingerprint) digests of every
        # program THIS process dispatched — empty unless
        # Settings.RANK_CONTRACTS armed the engine's recording.
        "program_digests": ranksafe.receipt(),
        "dcn_bytes_per_round": int(dcn_bytes),
        "metrics_snapshot": metrics_snapshot,
        "global": global_row.tolist(),
        "losses": fetch(losses)[:nodes].astype(np.float64).tolist(),
        "digest": digest,
        "devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "processes": jax.process_count(),
        "process_id": jax.process_index(),
        "hosts_axis": mesh_axis_size(mesh, HOST_AXIS) if mesh else 1,
        "mesh": dict(
            zip(mesh.axis_names, mesh.devices.shape)
        ) if mesh is not None else None,
    }


def worker_main() -> int:
    """Subprocess body: join the world, run the demo, write JSON."""
    # Join BEFORE touching anything that initializes jax backends —
    # jax.distributed.initialize must precede device queries.
    from tpfl.parallel.distributed import ensure_distributed

    ensure_distributed()
    cfg = json.loads(os.environ.get("TPFL_CROSSHOST_CFG", "{}") or "{}")
    _apply_knobs(cfg.get("knobs"))
    fork = cfg.get("fork_rank")
    result = demo_run(
        nodes=int(cfg.get("nodes", 8)),
        rounds=int(cfg.get("rounds", 2)),
        seed=int(cfg.get("seed", 0)),
        algorithm=str(cfg.get("algorithm", "fedavg")),
        fork_rank=int(fork) if fork is not None else None,
    )
    out = os.environ.get("TPFL_CROSSHOST_OUT")
    if out:
        path = f"{out}.{result['process_id']}.json"
        with open(path, "w") as f:
            json.dump(result, f)
    else:  # pragma: no cover - manual runs
        print(json.dumps(result))
    return 0


def launch(
    num_processes: int = 2,
    devices_per_proc: int = 4,
    nodes: int = 8,
    rounds: int = 2,
    seed: int = 0,
    algorithm: str = "fedavg",
    knobs: Optional[dict] = None,
    timeout: float = 420.0,
    fork_rank: Optional[int] = None,
) -> list[dict]:
    """Fork ``num_processes`` gloo workers and return their results.

    Each child gets ``devices_per_proc`` forced virtual CPU devices
    and joins a fresh coordinator on a free localhost port; the parent
    never initializes jax.distributed itself (its own backend state is
    untouched). Raises on any worker failure, with the worker's
    stderr tail in the message — the CI failure must say WHY a rank
    died, not just that it did.

    When the workers ran with ``RANK_CONTRACTS`` (via ``knobs``), each
    receipt carries the ordered program-dispatch digests and the
    parent verifies all ranks issued the identical sequence
    (:func:`tpfl.parallel.ranksafe.compare_receipts`) — a divergence
    raises with the first (rank, ordinal, key) witness instead of
    hanging a real fleet on DCN. ``fork_rank`` deliberately breaks one
    rank's sequence (see :func:`demo_run`) to prove the check fires.
    """
    port = free_port()
    out_prefix = os.path.join(
        tempfile.mkdtemp(prefix="tpfl_crosshost_"), "result"
    )
    # Children must see the forced device count BEFORE importing jax:
    # scrub any inherited force flag (the parent test process runs
    # under conftest's 8-device XLA_FLAGS) and set our own. One
    # process per chip: these gloo workers are CPU worlds, pinned
    # through their environment (JAX_PLATFORMS below) — a parent
    # holding a TPU never has children that try to open it.
    xla_flags = " ".join(
        tok
        for tok in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in tok
    )
    cfg = json.dumps(
        {
            "nodes": nodes,
            "rounds": rounds,
            "seed": seed,
            "algorithm": algorithm,
            "knobs": dict(knobs or {}),
            "fork_rank": fork_rank,
        }
    )
    procs = []
    for pid in range(num_processes):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                f"{xla_flags} "
                f"--xla_force_host_platform_device_count={devices_per_proc}"
            ).strip(),
            TPFL_COORDINATOR=f"127.0.0.1:{port}",
            TPFL_NUM_PROCESSES=str(num_processes),
            TPFL_PROCESS_ID=str(pid),
            TPFL_CROSSHOST_OUT=out_prefix,
            TPFL_CROSSHOST_CFG=cfg,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "tpfl.parallel.crosshost"],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    failures = []
    for pid, proc in enumerate(procs):
        try:
            _, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            _, err = proc.communicate()
            failures.append(f"rank {pid}: timeout\n{err[-2000:]}")
            continue
        if proc.returncode != 0:
            failures.append(
                f"rank {pid}: exit {proc.returncode}\n{err[-2000:]}"
            )
    if failures:
        raise RuntimeError(
            "crosshost workers failed:\n" + "\n---\n".join(failures)
        )
    results = []
    for pid in range(num_processes):
        with open(f"{out_prefix}.{pid}.json") as f:
            results.append(json.load(f))
    receipts = [r.get("program_digests") or [] for r in results]
    if any(receipts):
        # RANK_CONTRACTS receipts present: the fleet must have issued
        # ONE program sequence (ranksafe is pure stdlib — the parent
        # verifies without importing jax).
        from tpfl.parallel.ranksafe import compare_receipts

        compare_receipts(receipts)
    return results


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    sys.exit(worker_main())
