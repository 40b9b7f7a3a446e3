"""Seeded experiment harness — reproducibility + attack comparison.

Reference: ``exp_SAVE3.txt:116-185`` (``__train_with_seed``), ``:282-332``
(``test_global_training_reproducibility``: run two seeded experiments,
flatten the global metric tables, compare). The tpfl version is generic:
one entry point runs a seeded federation (optionally with adversaries),
returns the experiment's global metric table, and helpers flatten /
compare tables numerically.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

from tpfl.attacks.attacks import AttackFn, make_adversary

#: Ground-truth adversary registry: ``exp_name -> {addr: attack name}``
#: recorded by :func:`run_seeded_experiment` for every adversarial run.
#: This is what detection tests (``tests/test_ledger.py``,
#: ``tests/test_quarantine.py``) score the AnomalyScorer's flags against — the harness KNOWS who poisoned,
#: the ledger has to find them.
_ADVERSARIES: dict[str, dict[str, str]] = {}


def adversary_map(exp_name: str) -> dict[str, str]:
    """``{node addr: attack name}`` for a harness-run experiment
    (empty for fault-free runs / unknown experiments)."""
    return dict(_ADVERSARIES.get(exp_name, {}))


#: Final-model digests per experiment: ``exp_name -> {addr: sha256}``
#: of every node's parameter leaves at finish — the byte-determinism
#: receipt ``tests/test_async_control.py`` compares across same-seed
#: runs (and across nodes within one serialized run).
_FINAL_DIGESTS: dict[str, dict[str, str]] = {}


def final_model_digests(exp_name: str) -> dict[str, str]:
    """``{addr: sha256(params)}`` captured at experiment finish."""
    return dict(_FINAL_DIGESTS.get(exp_name, {}))


#: Per-experiment adaptive-controller trajectories:
#: ``exp_name -> {addr: [{round, k, deadline, ...}, ...]}`` captured
#: before teardown — the K/deadline determinism receipt (two same-seed
#: serialized runs must produce identical trajectories at every node).
_CTL_TRAJECTORIES: dict[str, dict[str, list]] = {}


def controller_trajectories(exp_name: str) -> dict[str, list]:
    """``{addr: per-round controller decisions}`` captured at
    experiment finish (empty for runs without ASYNC_ADAPTIVE)."""
    return {
        k: [dict(r) for r in v]
        for k, v in _CTL_TRAJECTORIES.get(exp_name, {}).items()
    }
from tpfl.learning.dataset import RandomIIDPartitionStrategy, rendered_digits
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


def run_seeded_experiment(
    seed: int,
    n: int,
    rounds: int,
    *,
    epochs: int = 1,
    adversaries: Optional[dict[int, AttackFn]] = None,
    attack_plan: Optional[Any] = None,
    fault_plan: Optional[Any] = None,
    speed_plan: Optional[Any] = None,
    aggregator_factory: Optional[Callable[[], Any]] = None,
    topology: TopologyType = TopologyType.STAR,
    model_fn: Optional[Callable[[int], Any]] = None,
    data_fn: Optional[Callable[[int], Any]] = None,
    samples_per_node: int = 300,
    learning_rate: float = 0.1,
    batch_size: int = 50,
    timeout: float = 240.0,
) -> str:
    """Run one seeded federation; returns the experiment name.

    ``adversaries`` maps node index -> attack (persistent, applied to
    every fit — see :class:`tpfl.attacks.AdversarialLearner`).
    ``attack_plan`` is the declarative alternative
    (:class:`tpfl.attacks.plan.AttackPlan`: which peers, which rounds,
    which attack, ramp/once/always — seeded, schedule-aware), and
    ``fault_plan`` (:class:`tpfl.communication.faults.FaultPlan`)
    composes network chaos into the same run; both plans' ground truth
    lands in :func:`adversary_map`. ``model_fn(seed)`` / ``data_fn
    (seed)`` override the default MLP / rendered-digits pair.
    Reference: star topology, seeded settings (exp_SAVE3.txt:116-156).
    """
    prev_seed = Settings.SEED
    Settings.SEED = seed
    # Reproducibility beats latency here: a vote/aggregation timeout
    # firing under host load would truncate the tally and elect a
    # different train set in one run but not the other — the exact
    # nondeterminism this harness exists to rule out.
    prev_vote, prev_agg = Settings.VOTE_TIMEOUT, Settings.AGGREGATION_TIMEOUT
    Settings.VOTE_TIMEOUT = max(prev_vote, 300.0)
    Settings.AGGREGATION_TIMEOUT = max(prev_agg, 300.0)
    nodes: list[Node] = []
    try:
        data = (
            data_fn(seed)
            if data_fn is not None
            else rendered_digits(
                n_train=samples_per_node * n,
                n_test=max(100, samples_per_node * n // 5),
                seed=seed,
            )
        )
        parts = data.generate_partitions(
            n, RandomIIDPartitionStrategy, seed=seed
        )
        for i in range(n):
            model = (
                model_fn(seed)
                if model_fn is not None
                else create_model("mlp", (28, 28), seed=seed)
            )
            # Pinned addresses: per-node shuffle/vote seeds derive from
            # the address, and table comparison aligns by node name —
            # auto-assigned (global-counter) names would make two
            # identical runs differ.
            node = Node(
                model,
                parts[i],
                addr=f"seed{seed}-n{i}",
                aggregator=(
                    aggregator_factory() if aggregator_factory else None
                ),
                learning_rate=learning_rate,
                batch_size=batch_size,
            )
            if adversaries and i in adversaries:
                make_adversary(node, adversaries[i])
            nodes.append(node)

        # Declarative chaos: scheduled adversaries + network faults in
        # one spec, wired BEFORE start (learners wrap unstarted nodes).
        plan_truth: dict[str, str] = {}
        if (
            attack_plan is not None
            or fault_plan is not None
            or speed_plan is not None
        ):
            from tpfl.attacks.plan import apply_chaos

            plan_truth, _ = apply_chaos(
                nodes, attack_plan=attack_plan, fault_plan=fault_plan,
                speed_plan=speed_plan, seed=seed,
            )
        for node in nodes:
            node.start()

        matrix = TopologyFactory.generate_matrix(topology, n)
        TopologyFactory.connect_nodes(matrix, nodes)
        wait_convergence(nodes, n - 1, only_direct=False, wait=30)
        exp_name = nodes[0].set_start_learning(rounds=rounds, epochs=epochs)
        if adversaries or plan_truth:
            # Ground truth for detection tests: who actually
            # poisons this experiment, by node address — derived from
            # the plan when one is given.
            truth = dict(plan_truth)
            for i, fn in (adversaries or {}).items():
                truth[nodes[i].addr] = str(
                    getattr(fn, "name", getattr(fn, "__name__", "attack"))
                )
            _ADVERSARIES[exp_name] = truth
        wait_to_finish(nodes, timeout=timeout)
        # Byte-determinism receipt: digest every node's final params
        # BEFORE stop() tears anything down (leaf_bytes: the sanctioned
        # zero-copy byte view — hashlib consumes the memoryview).
        import hashlib

        import jax as _jax

        from tpfl.learning.serialization import leaf_bytes

        digests: dict[str, str] = {}
        for node in nodes:
            h = hashlib.sha256()
            for leaf in _jax.tree_util.tree_leaves(
                node.learner.get_model().get_parameters()
            ):
                h.update(leaf_bytes(np.asarray(leaf)))
            digests[node.addr] = h.hexdigest()
        _FINAL_DIGESTS[exp_name] = digests
        # Adaptive-controller trajectory receipt (empty lists when
        # ASYNC_ADAPTIVE was off — the controller records nothing).
        # Experiment teardown (RoundFinishedStage -> state.clear) has
        # already reset the controller by the time the last node
        # finishes, so read the archived log when the live one is gone.
        _CTL_TRAJECTORIES[exp_name] = {
            node.addr: (
                node.state.async_controller.trajectory()
                or node.state.async_controller.last_trajectory()
            )
            for node in nodes
        }
        return exp_name
    finally:
        for node in nodes:
            node.stop()
        Settings.SEED = prev_seed
        Settings.VOTE_TIMEOUT = prev_vote
        Settings.AGGREGATION_TIMEOUT = prev_agg


def metric_table(exp_name: str) -> dict[str, dict[str, list]]:
    """The experiment's global metric table:
    ``{node: {metric: [(round, value), ...]}}``."""
    return logger.get_global_logs().get(exp_name, {})


def flatten_table(table: dict[str, dict[str, list]]) -> np.ndarray:
    """Deterministic numeric flattening (reference __flatten_results,
    exp_SAVE3.txt:335-336 region): sort by node then metric then round."""
    out: list[float] = []
    for node in sorted(table):
        for metric in sorted(table[node]):
            for rnd, value in sorted(table[node][metric]):
                out.append(float(value))
    return np.asarray(out, dtype=np.float64)


def _series_maps(
    table: dict[str, dict[str, list]],
) -> dict[tuple[str, str], dict[int, float]]:
    return {
        (node, metric): {int(r): float(v) for r, v in series}
        for node, metrics in table.items()
        for metric, series in metrics.items()
        if series
    }


def assert_tables_allclose(
    a: dict[str, dict[str, list]],
    b: dict[str, dict[str, list]],
    atol: float = 1e-3,
) -> None:
    """Two seeded runs must produce numerically identical metric tables
    up to float-reduction noise.

    Compared per (node, metric) at every COMMON round: metric gossip is
    best-effort (a flooded MetricsCommand can be lost under load), so
    one run may simply be missing a round's entry — comparing
    "whatever came last" would then compare different rounds. For truly
    seeded-identical runs, values at every shared round must agree.
    Aggregation math is canonically ordered (aggregator.py sorts by
    contributors), but with partial aggregation the gossip *merge
    topology* — which partial aggregates formed before full coverage —
    still depends on scheduling, giving ~1e-4 drift over a few rounds.
    Real divergence (seed/behavior differences) shows at 1e-1 scale;
    the default atol sits between. The reference never asserted at all
    (its np.allclose is commented out, exp_SAVE3.txt:301)."""
    ma, mb = _series_maps(a), _series_maps(b)
    if set(ma) != set(mb):
        raise AssertionError(
            f"Metric tables differ in keys: only-in-a="
            f"{sorted(set(ma) - set(mb))}, only-in-b={sorted(set(mb) - set(ma))}"
        )
    got, want, labels = [], [], []
    for key in sorted(ma):
        common = set(ma[key]) & set(mb[key])
        if not common:
            raise AssertionError(f"No common rounds for {key}")
        for r in sorted(common):  # EVERY shared round must agree
            got.append(ma[key][r])
            want.append(mb[key][r])
            labels.append((key, r))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=atol,
        err_msg=f"compared (key, round): {labels}",
    )
