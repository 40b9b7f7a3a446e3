"""Large-scale in-process federation — BASELINE config 4 on the
protocol path.

The reference reaches large node counts by multiplexing logical nodes
over a Ray actor pool (``simulation/actor_pool.py:69``). tpfl's
equivalent: every node is a real protocol participant (vote, gossip,
heartbeats), but concurrent ``fit()`` calls batch into one vmapped XLA
program through :mod:`tpfl.simulation`. Partial participation falls out
of the protocol itself — the vote elects ``Settings.TRAIN_SET_SIZE``
nodes per round.

Run: ``tpfl experiment run scale -- --nodes 100 --rounds 2`` (or
``python -m tpfl.examples.scale``). Prints per-round wall time and
rounds/sec at the end.

Scale envelope: the protocol layer is Python threads, so its ceiling is
host cores, not the TPU. A single STAR hub relays every flooded message
to all N-1 peers (O(N^2) handler work at one node) and saturates around
~200 nodes; the default TREE topology (star-of-stars, ~sqrt(N) fully
meshed hubs — tpfl.utils.topologies) splits the relay load across hubs
and sustains 500+ protocol nodes (measured: see README). Beyond that,
use the vmapped path directly
(``VmapFederation`` with a participation mask — the whole round is one
XLA program and the protocol overhead disappears) or the hierarchical
``FederationLearner`` tier.
"""

from __future__ import annotations

import argparse
import time

from tpfl.learning.dataset import RandomIIDPartitionStrategy, rendered_digits
from tpfl.models import create_model
from tpfl.node import Node
from tpfl.settings import Settings
from tpfl.utils import (
    TopologyFactory,
    TopologyType,
    wait_convergence,
    wait_to_finish,
)


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="Large-scale in-process federation (config 4 tier)."
    )
    p.add_argument("--nodes", type=int, default=100)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument(
        "--train-set-size",
        type=int,
        default=10,
        help="Elected trainers per round (partial participation).",
    )
    p.add_argument("--samples-per-node", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seed", type=int, default=666)
    p.add_argument(
        "--topology",
        choices=["star", "tree"],
        default="tree",
        help="star = single hub (reference-style, ~200-node ceiling); "
        "tree = sqrt(N) meshed hubs (default, 500+ nodes).",
    )
    p.add_argument(
        "--heartbeat-period",
        type=float,
        default=10.0,
        help="Digest heartbeat cadence (s). Full-view discovery takes "
        "O(topology diameter) periods before learning starts; lower it "
        "for small/quick runs, keep 10s at hundreds of nodes (beat "
        "relay load scales with N).",
    )
    p.add_argument(
        "--election",
        choices=["vote", "hash"],
        default="hash",
        help="vote = reference protocol (O(N^2) vote flood + timeout "
        "waits); hash = deterministic sortition (default here: zero "
        "election traffic, recommended at scale).",
    )
    return p.parse_args(argv)


def scale(args: argparse.Namespace) -> dict[str, float]:
    Settings.set_scale_settings()
    Settings.from_env()  # TPFL_* overrides (CLI --profile rides these)
    Settings.TRAIN_SET_SIZE = args.train_set_size
    Settings.ELECTION = args.election
    # Digest-based membership costs O(edges) per period (heartbeater
    # docstring), so the cadence no longer needs to scale with N — but
    # full-view convergence takes O(diameter) periods and O(N) digest
    # entries must be merged per beat at hubs, so keep a relaxed beat
    # and a timeout that tolerates a single-core host's GIL being
    # monopolized by a vote flood or a batched-fit dispatch for tens of
    # seconds.
    Settings.HEARTBEAT_PERIOD = args.heartbeat_period
    # The timeout must also scale with N: at 1000 single-core nodes
    # the formation phase monopolizes the GIL long enough that beats
    # starve past a flat 120 s, and the resulting eviction storm
    # (~2000 false evictions measured) tears hub links out of the
    # very topology the diffusion needs. In-process nodes cannot die
    # unannounced, so a generous timeout costs nothing here.
    Settings.HEARTBEAT_TIMEOUT = max(
        120.0, 12 * args.heartbeat_period, 0.6 * args.nodes
    )
    # Partial-model exchange among the elected trainers serializes on
    # the GIL with every other node's threads. A flat 120 s wait makes
    # nearly every node time out before an aggregate even exists, but
    # an oversized budget is the round-length floor for every waiter
    # the diffusion wave misses — with the stall exit forming partial
    # aggregates early (Settings.AGGREGATION_STALL) and the epidemic
    # relay covering ~99% of nodes within minutes, 0.3 s/node bounds
    # the straggler tail without starving formation.
    Settings.AGGREGATION_TIMEOUT = max(120.0, 0.3 * args.nodes)

    n = args.nodes
    ds = rendered_digits(
        n_train=args.samples_per_node * n, n_test=200, seed=args.seed
    )
    parts = ds.generate_partitions(n, RandomIIDPartitionStrategy, seed=args.seed)
    print(f"Building {n} nodes...")
    nodes = [
        Node(
            create_model("mlp", (28, 28), seed=args.seed, hidden_sizes=(64,)),
            parts[i],
            simulation=True,
            batch_size=args.batch_size,
        )
        for i in range(n)
    ]
    t_start = time.monotonic()
    for nd in nodes:
        nd.start()
    try:
        # Hub-based topologies keep connectivity O(N) (a FULL mesh of
        # 1000 nodes would be ~500k in-process links); TREE additionally
        # spreads relay work over ~sqrt(N) hubs.
        topo = (
            TopologyType.TREE if args.topology == "tree" else TopologyType.STAR
        )
        matrix = TopologyFactory.generate_matrix(topo, n)
        TopologyFactory.connect_nodes(matrix, nodes)
        # Full-view discovery rides the heartbeat flood: every node must
        # hear N-1 others through the hub, so budget scales with N.
        wait_convergence(nodes, n - 1, only_direct=False, wait=max(120, n))
        t_ready = time.monotonic()
        print(f"Topology converged in {t_ready - t_start:.1f}s; starting...")

        nodes[0].set_start_learning(rounds=args.rounds, epochs=args.epochs)
        wait_to_finish(nodes, timeout=3600)
        t_done = time.monotonic()

        # Model agreement: "all nodes finished" alone can hide nodes
        # that timed out of the aggregation wait and ended the round on
        # their round-start weights. Report how many hold the majority
        # final model so the RESULT line is honest about convergence.
        import hashlib
        from collections import Counter

        import numpy as _np

        def model_digest(nd) -> str:
            from tpfl.learning.serialization import leaf_bytes

            h = hashlib.sha256()
            for leaf in nd.learner.get_model().get_parameters_list():
                h.update(leaf_bytes(_np.asarray(leaf, _np.float32)))
            return h.hexdigest()[:12]

        tally = Counter(model_digest(nd) for nd in nodes)
        agreement = tally.most_common(1)[0][1] / n

        rounds_per_sec = args.rounds / (t_done - t_ready)
        stats = {
            "nodes": n,
            "rounds": args.rounds,
            "election": args.election,
            "train_set_size": args.train_set_size,
            "setup_s": round(t_ready - t_start, 1),
            "learn_s": round(t_done - t_ready, 1),
            "rounds_per_sec": round(rounds_per_sec, 4),
            "model_agreement": round(agreement, 3),
        }
        print("RESULT:", stats)
        return stats
    finally:
        for nd in nodes:
            nd.stop()


def main(argv: list[str] | None = None) -> None:
    from tpfl.examples import start_on_device

    args = parse_args(argv)
    start_on_device()
    scale(args)


if __name__ == "__main__":
    main()
