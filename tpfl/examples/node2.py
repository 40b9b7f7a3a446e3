"""Two-process gRPC quickstart — the driving half.

Parity with reference ``p2pfl/examples/node2.py``: start a second node,
connect to a running node1 over real gRPC, kick off learning, and exit
when the experiment finishes. See node1.py for the full recipe.
"""

from __future__ import annotations

import argparse
import time

from tpfl.communication.grpc_transport import GrpcCommunicationProtocol
from tpfl.learning.dataset import rendered_digits
from tpfl.models import create_model
from tpfl.node import Node
from tpfl.settings import Settings
from tpfl.utils import wait_to_finish


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="tpfl gRPC quickstart (driving node).")
    p.add_argument("--port", type=int, required=True)
    p.add_argument(
        "--host", type=str, default="127.0.0.1",
        help="Bind address (0.0.0.0 inside containers so "
        "published ports are reachable).",
    )
    p.add_argument("--connect-to", type=str, required=True, help="host:port of node1")
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--samples", type=int, default=800)
    p.add_argument("--seed", type=int, default=667)
    return p.parse_args(argv)


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    from tpfl.examples import start_on_device

    start_on_device()
    Settings.set_standalone_settings()
    Settings.from_env()  # TPFL_* overrides (CLI --profile rides these)
    node = Node(
        create_model("mlp", (28, 28), seed=args.seed),
        rendered_digits(n_train=args.samples, n_test=200, seed=args.seed),
        protocol=GrpcCommunicationProtocol(f"{args.host}:{args.port}"),
    )
    node.start()
    if not node.connect(args.connect_to):
        node.stop()
        raise SystemExit(f"Could not connect to {args.connect_to}")
    time.sleep(2)  # let the handshake/gossip settle (reference node2.py sleeps too)
    node.set_start_learning(rounds=args.rounds, epochs=args.epochs)
    try:
        wait_to_finish([node], timeout=3600)
        print("Final metrics:", node.learner.evaluate())
    finally:
        node.stop()


if __name__ == "__main__":
    main()
