"""Attack injection — the fork's raison d'être (SURVEY §2.8).

The reference corrupts one node's weights after init
(``exp_SAVE3.txt:60-113`` sign-flip, ``:187-234`` additive noise) and
measures the effect on federation metrics. Here attacks are first-class:

- pure, jit-friendly parameter transforms (:func:`sign_flip`,
  :func:`additive_noise`) applied through
  ``TpflModel.apply_to_params``;
- :func:`poison_model` — one-shot corruption (reference parity);
- :class:`AdversarialLearner` — a persistent model-poisoning adversary
  that re-applies its attack to every local fit before the update
  enters aggregation (the threat model Krum/TrimmedMean defend
  against; the robust aggregators live in
  ``tpfl.learning.aggregators.robust``);
- :class:`AttackPlan` / :class:`PlannedAdversary` /
  :func:`apply_chaos` (``tpfl.attacks.plan``) — declarative seeded
  per-peer attack SCHEDULES (which peers, which rounds, which attack,
  ramp/once/always), the adversarial mirror of
  :class:`~tpfl.communication.faults.FaultPlan`, composable with a
  fault plan into one chaos spec and carrying the ground-truth
  ``adversary_map`` detection tests score against.

See :mod:`tpfl.attacks.harness` for the seeded reproducibility harness
(``exp_SAVE3.txt:282-332``).
"""

from tpfl.attacks.attacks import (
    AdversarialLearner,
    additive_noise,
    make_adversary,
    poison_model,
    sign_flip,
)
from tpfl.attacks.harness import (
    adversary_map,
    assert_tables_allclose,
    controller_trajectories,
    flatten_table,
    metric_table,
    run_seeded_experiment,
)
from tpfl.attacks.plan import (
    AttackPlan,
    AttackSpec,
    PlannedAdversary,
    SlowLearner,
    apply_attack_plan,
    apply_chaos,
    apply_speed_plan,
)

__all__ = [
    "sign_flip",
    "additive_noise",
    "poison_model",
    "AdversarialLearner",
    "make_adversary",
    "AttackPlan",
    "AttackSpec",
    "PlannedAdversary",
    "SlowLearner",
    "apply_attack_plan",
    "apply_chaos",
    "apply_speed_plan",
    "run_seeded_experiment",
    "adversary_map",
    "controller_trajectories",
    "metric_table",
    "flatten_table",
    "assert_tables_allclose",
]
