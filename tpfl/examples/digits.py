"""Configurable multi-node federated experiment on rendered digit images.

Parity with the reference's flagship example
(``p2pfl/examples/mnist.py:73-297``): pick node count, rounds, epochs,
topology, transport, aggregator and model from the command line, run a
full in-process federation, then print the recorded local/global metric
tables. Differences are deliberate:

- Data is :func:`tpfl.learning.dataset.rendered_digits` (real rendered
  glyph images) instead of an HF-hub MNIST download — hermetic, zero
  egress (see rendered.py's module docstring).
- ``--framework`` is gone: there is one jitted JAX learner.
- Metrics print as tables instead of blocking ``plt.show()`` windows.

Run directly (``python -m tpfl.examples.digits --nodes 4``) or through
the CLI (``tpfl experiment run digits -- --nodes 4``).
"""

from __future__ import annotations

import argparse
import time

from tpfl.communication.grpc_transport import GrpcCommunicationProtocol
from tpfl.communication.memory import InMemoryCommunicationProtocol
from tpfl.examples import start_on_device
from tpfl.learning.aggregators import (
    FedAvg,
    FedMedian,
    FedProx,
    Krum,
    Scaffold,
    TrimmedMean,
)
from tpfl.learning.dataset import (
    DirichletPartitionStrategy,
    RandomIIDPartitionStrategy,
    rendered_digits,
)
from tpfl.management.logger import logger
from tpfl.models import create_model
from tpfl.node import Node
from tpfl.settings import Settings
from tpfl.utils import (
    TopologyFactory,
    TopologyType,
    wait_convergence,
    wait_to_finish,
)

AGGREGATORS = {
    "fedavg": FedAvg,
    "fedmedian": FedMedian,
    "scaffold": Scaffold,
    "fedprox": FedProx,
    "krum": Krum,
    "trimmedmean": TrimmedMean,
}
PROTOCOLS = {
    "memory": InMemoryCommunicationProtocol,
    "grpc": GrpcCommunicationProtocol,
}


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="tpfl rendered-digits experiment (reference mnist.py parity)."
    )
    p.add_argument("--nodes", type=int, default=2)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--protocol", choices=sorted(PROTOCOLS), default="memory")
    p.add_argument("--aggregator", choices=sorted(AGGREGATORS), default="fedavg")
    p.add_argument(
        "--topology",
        choices=[t.value for t in TopologyType],
        default="line",
    )
    p.add_argument("--model", choices=["mlp", "cnn"], default="mlp")
    p.add_argument(
        "--partitioning", choices=["iid", "dirichlet"], default="iid"
    )
    p.add_argument("--samples-per-node", type=int, default=800)
    p.add_argument("--batch-size", type=int, default=50)
    p.add_argument("--learning-rate", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=666)
    p.add_argument(
        "--simulation",
        action="store_true",
        help="Batch concurrent node fits into one vmapped XLA program "
        "(the scale-out path; reference --disable_ray inverted).",
    )
    p.add_argument("--show-metrics", action="store_true", default=True)
    p.add_argument(
        "--no-show-metrics", dest="show_metrics", action="store_false"
    )
    p.add_argument("--measure-time", action="store_true")
    p.add_argument(
        "--profiling",
        action="store_true",
        help="cProfile the experiment; writes digits.prof + prints the "
        "top cumulative entries (reference mnist.py --profiling uses "
        "yappi, unavailable here).",
    )
    p.add_argument(
        "--profile",
        metavar="DIR",
        default=None,
        help="write a jax.profiler device trace of the whole experiment "
        "to DIR (view with TensorBoard/xprof) — the TPU-native profiling "
        "path; --profiling covers host-side Python instead.",
    )
    args = p.parse_args(argv)
    args.topology = TopologyType(args.topology)
    return args


def _print_metric_tables() -> None:
    """Text rendition of the reference's metric plots (mnist.py:212-252)."""
    local = logger.get_local_logs()
    if local:
        print("\n=== Local metrics (per round / node / metric) ===")
        for exp, rounds in local.items():
            for rnd, nodes in sorted(rounds.items()):
                for node, metrics in sorted(nodes.items()):
                    for metric, values in sorted(metrics.items()):
                        last = values[-1][1] if values else float("nan")
                        print(
                            f"  [{exp}] round={rnd} {node} "
                            f"{metric}: {last:.4f} ({len(values)} points)"
                        )
    global_logs = logger.get_global_logs()
    if global_logs:
        print("\n=== Global metrics (per node / metric) ===")
        for exp, nodes in global_logs.items():
            for node, metrics in sorted(nodes.items()):
                for metric, values in sorted(metrics.items()):
                    series = ", ".join(f"{r}:{v:.4f}" for r, v in values)
                    print(f"  [{exp}] {node} {metric}: {series}")


def digits(args: argparse.Namespace) -> list[Node]:
    """Build, connect, run and tear down the federation. Returns the
    (stopped) nodes so tests can inspect final models/metrics."""
    if getattr(args, "profile", None):
        import jax

        with jax.profiler.trace(args.profile):
            result = digits(
                argparse.Namespace(**{**vars(args), "profile": None})
            )
        print(f"jax profiler trace written to {args.profile}")
        return result
    start = time.monotonic()
    Settings.set_standalone_settings()
    # TPFL_* environment overrides apply AFTER the profile, so the
    # CLI can steer any knob (tpfl experiment run --profile DIR rides
    # TPFL_PROFILING_TRACE_DIR through here).
    Settings.from_env()

    n = args.nodes
    ds = rendered_digits(
        n_train=args.samples_per_node * n,
        n_test=max(100, args.samples_per_node * n // 5),
        seed=args.seed,
    )
    strategy = (
        RandomIIDPartitionStrategy
        if args.partitioning == "iid"
        else DirichletPartitionStrategy
    )
    parts = ds.generate_partitions(n, strategy, seed=args.seed)

    input_shape = (28, 28)
    nodes = []
    for i in range(n):
        model = create_model(args.model, input_shape, seed=args.seed)
        nodes.append(
            Node(
                model,
                parts[i],
                protocol=PROTOCOLS[args.protocol],
                aggregator=AGGREGATORS[args.aggregator](),
                simulation=args.simulation,
                learning_rate=args.learning_rate,
                batch_size=args.batch_size,
            )
        )
    for nd in nodes:
        nd.start()
    try:
        matrix = TopologyFactory.generate_matrix(args.topology, n)
        TopologyFactory.connect_nodes(matrix, nodes)
        wait_convergence(nodes, n - 1, only_direct=False, wait=60)

        if args.rounds < 1:
            raise ValueError("rounds must be >= 1")
        nodes[0].set_start_learning(rounds=args.rounds, epochs=args.epochs)
        wait_to_finish(nodes, timeout=3600)

        if args.show_metrics:
            _print_metric_tables()
        accs = {
            nd.addr: nd.learner.evaluate()["test_metric"] for nd in nodes
        }
        print("\nFinal test accuracy per node:")
        for addr, acc in accs.items():
            print(f"  {addr}: {acc:.4f}")
    finally:
        for nd in nodes:
            nd.stop()
        if args.measure_time:
            print(f"--- {time.monotonic() - start:.1f} seconds ---")
    return nodes


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    start_on_device()
    if args.profiling:
        import cProfile
        import pstats

        prof = cProfile.Profile()
        prof.enable()
        try:
            digits(args)
        finally:
            prof.disable()
            prof.dump_stats("digits.prof")
            pstats.Stats(prof).sort_stats("cumulative").print_stats(20)
    else:
        digits(args)


if __name__ == "__main__":
    main()
