"""Multi-host / multi-slice deployment — BASELINE config 5.

Two ways to span hosts, one entry point:

**Engine mode (the default on pods)** — every process joins ONE
``jax.distributed`` world and the :class:`tpfl.parallel
.FederationEngine` lays a 3D ``hosts x nodes [x model]`` mesh over the
global device list (``SHARD_HOSTS=0`` auto-resolves to the process
count). The ENTIRE federation — every host's local nodes — folds in
one SPMD program: the nodes leg rides ICI, the hosts leg rides DCN,
and ``ENGINE_WIRE_CODEC`` quantizes the DCN partials in-program
(docs/scaling.md "3D mesh & cross-host DCN"). Rank 0 reports.

Terminal 1:  python -m tpfl.examples.multislice --coordinator 127.0.0.1:8476 \
    --num-processes 2 --process-id 0 --rounds 2
Terminal 2:  python -m tpfl.examples.multislice --coordinator 127.0.0.1:8476 \
    --num-processes 2 --process-id 1 --rounds 2

(On Cloud TPU pods the runtime supplies the coordinator — run the same
command with no ``--coordinator`` on every worker and ``--mode
engine``; see docs/deployment.md.)

**gRPC fallback (``--mode grpc``)** — the historical slice-aggregate
topology, kept for deployments without a shared jax.distributed world
(mixed hardware, firewalled DCN): each process is ONE protocol Node
whose learner is a :class:`tpfl.parallel.FederationLearner` — local
nodes train as a single vmapped XLA program, and only the slice-level
aggregate crosses hosts over gRPC. Gossip traffic is O(hosts), but the
cross-host fold is a protocol aggregate, not an in-program collective.

Terminal 1 (passive slice):   python -m tpfl.examples.multislice --port 6700
Terminal 2 (driving slice):   python -m tpfl.examples.multislice \
    --port 6701 --connect-to 127.0.0.1:6700 --rounds 2

``--mode auto`` (default) picks engine when a coordinator is
configured (flag or ``TPFL_COORDINATOR`` env), else gRPC.
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

from tpfl.learning.dataset import rendered_digits
from tpfl.models import create_model
from tpfl.settings import Settings


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="tpfl multi-slice quickstart.")
    p.add_argument(
        "--mode", choices=("auto", "engine", "grpc"), default="auto",
        help="engine = one jax.distributed SPMD world (3D mesh, DCN "
        "collectives); grpc = per-slice protocol Nodes (fallback); "
        "auto = engine iff a coordinator is configured.",
    )
    p.add_argument(
        "--coordinator", type=str, default=None,
        help="host:port of the jax.distributed coordinator (engine "
        "mode; TPFL_COORDINATOR env works too).",
    )
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument(
        "--port", type=int, default=None,
        help="gRPC bind port (grpc mode only).",
    )
    p.add_argument(
        "--host", type=str, default="127.0.0.1",
        help="Bind address (0.0.0.0 inside containers so "
        "published ports are reachable).",
    )
    p.add_argument("--connect-to", type=str, default=None, help="host:port of a running slice (driving role, grpc mode)")
    p.add_argument("--local-nodes", type=int, default=8)
    p.add_argument("--local-rounds", type=int, default=1)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--samples", type=int, default=2000)
    p.add_argument("--seed", type=int, default=666)
    return p.parse_args(argv)


def _node_stack(ds, n_nodes: int, seed: int, batch_size: int = 32):
    """[n, n_batches, b, ...] host stacks from IID partitions (the
    FederationLearner staging, inlined for the engine path)."""
    from tpfl.learning.dataset.partition_strategies import (
        RandomIIDPartitionStrategy,
    )

    parts = ds.generate_partitions(n_nodes, RandomIIDPartitionStrategy, seed=seed)
    xs, ys = [], []
    for part in parts:
        x, y = part.export(batch_size=batch_size, train=True).stacked()
        xs.append(x)
        ys.append(y)
    n_batches = min(x.shape[0] for x in xs)
    return (
        np.stack([x[:n_batches] for x in xs]),
        np.stack([y[:n_batches] for y in ys]),
    )


def run_engine(args: argparse.Namespace) -> None:
    """The distributed-engine path: one SPMD federation over every
    process' devices, hosts leg on DCN. Identical host inputs on every
    rank (seeded), so the run needs no data plane beyond jax itself."""
    # Join BEFORE any backend query — jax.distributed.initialize must
    # precede device use.
    from tpfl.parallel.distributed import ensure_distributed, local_data

    ensure_distributed(
        args.coordinator, args.num_processes, args.process_id
    )
    import jax

    from tpfl.examples import start_on_device

    start_on_device()
    Settings.set_standalone_settings()
    Settings.from_env()  # TPFL_* overrides (CLI --profile rides these)
    Settings.SHARD_NODES = True
    Settings.SHARD_HOSTS = 0  # auto: one hosts-row per process

    from tpfl.parallel.engine import FederationEngine, auto_mesh
    from tpfl.parallel.mesh import HOST_AXIS, mesh_axis_size

    n = args.local_nodes * max(jax.process_count(), 1)
    ds = rendered_digits(n_train=args.samples, n_test=400, seed=args.seed)
    xs, ys = _node_stack(ds, n, seed=args.seed)

    mesh = auto_mesh()
    eng = FederationEngine(
        create_model("mlp", (28, 28), seed=args.seed).module,
        n, mesh=mesh, seed=args.seed,
    )
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(xs, ys)
    t0 = time.monotonic()
    p, losses = eng.run_rounds(
        p, dx, dy, n_rounds=args.rounds, epochs=args.epochs, donate=False
    )
    wall = time.monotonic() - t0
    if jax.process_index() == 0:
        hosts = mesh_axis_size(mesh, HOST_AXIS) if mesh is not None else 1
        shape = (
            dict(zip(mesh.axis_names, mesh.devices.shape))
            if mesh is not None else {"devices": 1}
        )
        print(
            f"engine mode: {n} nodes over mesh {shape} "
            f"({jax.process_count()} processes, hosts axis {hosts})"
        )
        print(
            f"{args.rounds} rounds in {wall:.2f}s — "
            f"last-round mean loss {float(np.mean(local_data(losses))):.4f}"
        )


def run_grpc(args: argparse.Namespace) -> None:
    """The gRPC fallback: per-slice protocol Nodes, slice aggregates
    over the wire (the pre-ISSUE-18 topology, kept for deployments
    without a shared jax.distributed world)."""
    from tpfl.communication.grpc_transport import GrpcCommunicationProtocol
    from tpfl.node import Node
    from tpfl.parallel import FederationLearner
    from tpfl.utils import wait_to_finish

    if args.port is None:
        raise SystemExit("grpc mode needs --port")
    from tpfl.examples import start_on_device

    start_on_device()
    Settings.set_standalone_settings()
    Settings.from_env()  # TPFL_* overrides (CLI --profile rides these)
    node = Node(
        create_model("mlp", (28, 28), seed=args.seed),
        rendered_digits(n_train=args.samples, n_test=400, seed=args.seed + args.port),
        protocol=GrpcCommunicationProtocol(f"{args.host}:{args.port}"),
        learner=FederationLearner(
            n_local_nodes=args.local_nodes,
            local_rounds=args.local_rounds,
            seed=args.seed,
        ),
    )
    node.start()
    try:
        if args.connect_to is None:
            print(f"Slice listening on {node.addr} ({args.local_nodes} local nodes); Ctrl-C to stop")
            while True:
                time.sleep(1)
        else:
            if not node.connect(args.connect_to):
                raise SystemExit(f"Could not connect to {args.connect_to}")
            time.sleep(2)
            node.set_start_learning(rounds=args.rounds, epochs=args.epochs)
            wait_to_finish([node], timeout=3600)
            print("Slice-level metrics:", node.learner.evaluate())
    except KeyboardInterrupt:
        pass
    finally:
        node.stop()


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    mode = args.mode
    if mode == "auto":
        mode = (
            "engine"
            if (args.coordinator or os.environ.get("TPFL_COORDINATOR"))
            else "grpc"
        )
    if mode == "engine":
        run_engine(args)
    else:
        run_grpc(args)


if __name__ == "__main__":
    main()
