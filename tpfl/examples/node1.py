"""Two-process gRPC quickstart — the passive half.

Parity with reference ``p2pfl/examples/node1.py``: start one node on a
real gRPC port and wait for a peer (node2) to connect and drive the
experiment. Run in two terminals::

    python -m tpfl.examples.node1 --port 6666
    python -m tpfl.examples.node2 --port 6661 --connect-to 127.0.0.1:6666
"""

from __future__ import annotations

import argparse
import time

from tpfl.communication.grpc_transport import GrpcCommunicationProtocol
from tpfl.learning.dataset import rendered_digits
from tpfl.models import create_model
from tpfl.node import Node
from tpfl.settings import Settings


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="tpfl gRPC quickstart (passive node).")
    p.add_argument("--port", type=int, required=True)
    p.add_argument(
        "--host", type=str, default="127.0.0.1",
        help="Bind address (0.0.0.0 inside containers so "
        "published ports are reachable).",
    )
    p.add_argument("--samples", type=int, default=800)
    p.add_argument("--seed", type=int, default=666)
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
    print(f"Node listening on {node.addr}; waiting for peers (Ctrl-C to stop)")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        node.stop()


if __name__ == "__main__":
    main()
