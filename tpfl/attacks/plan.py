"""Declarative, seeded per-peer attack schedules — the adversarial
mirror of :class:`tpfl.communication.faults.FaultPlan`.

PR 2 made *network* chaos declarative and reproducible (drop / delay /
corrupt / crash / partition, per-link RNG streams); this module does
the same for *learning-plane* adversaries. An :class:`AttackPlan` names
which peers attack, with which attack, over which rounds, at what
intensity (``always`` / ``once`` / ``ramp``), and every noise draw
derives from ``(seed, peer, round, leaf)`` — two same-(seed, plan) runs
poison byte-identically regardless of thread interleaving (the closure
counter in :func:`tpfl.attacks.attacks.additive_noise` could not
guarantee that when an instance was shared).

Composition: :func:`apply_chaos` installs an attack plan AND a fault
plan on one federation in one call — malicious peers coexist with
drops, crashes and partitions in a single chaos spec, the way
pfl-research treats adversarial simulation as a first-class tier and
BlazeFL demands the run stay deterministic. The plan is also the
**ground truth**: :meth:`AttackPlan.adversary_map` is what detection /
quarantine tests score against (the plan KNOWS who poisons; the
defense has to find them).

Schema (:meth:`AttackPlan.from_dict`)::

    {"seed": 7,
     "peers": {"node-3": {"attack": "sign_flip"},
               "node-6": {"attack": "additive_noise", "std": 0.1,
                           "mode": "ramp", "start": 2, "ramp_rounds": 3},
               "node-7": {"attack": "stale_flood"},
               "node-8": {"attack": "withhold_replay", "start": 2,
                           "end": 5},
               "1":      {"attack": "sign_flip", "mode": "once",
                           "start": 0}}}

The async buffer-stuffing modes (``stale_flood`` / ``withhold_replay``
— see :data:`REPLAY_ATTACKS`) poison the freshness METADATA instead of
the parameters: the adversary replays an old contribution under its
old version tag, instantly, to crowd honest arrivals out of the
buffered round's K slots.

Peer keys are node addresses, or integer indices resolved against the
node list at :func:`apply_attack_plan` time (the harness's seeded
addresses are positional).
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Iterable, Optional, Sequence

from tpfl.attacks.attacks import AdversarialLearner
from tpfl.settings import Settings

ATTACKS = ("sign_flip", "additive_noise", "stale_flood", "withhold_replay")
MODES = ("always", "once", "ramp")

#: Async buffer-stuffing attacks: the adversary caches its FIRST
#: contribution (params + the version ordinal it trained from) and,
#: while the schedule is active, REPLAYS it instead of fitting —
#: instantly, so the junk contribution races honest trainers into the
#: K-slot buffer. ``stale_flood`` starts at round 0 by convention: the
#: replayed tag's staleness ``τ`` grows without bound (the
#: implausible-τ signature). ``withhold_replay`` starts later
#: (``start > 0``): the peer first contributes honestly at advancing
#: versions, then replays the old one — a version REGRESSION
#: (``tpfl.management.ledger``'s ``stale_flood`` anomaly class catches
#: both). Parameters are never numerically poisoned; the attack is on
#: the freshness metadata and the buffer economy, which is why
#: staleness-BLIND aggregation folds it at full weight. Async rounds
#: only (sync rounds have no version tags); in a sync lifecycle the
#: replay degrades to re-sending stale params.
REPLAY_ATTACKS = ("stale_flood", "withhold_replay")


@dataclass
class AttackSpec:
    """One peer's attack schedule.

    ``mode``: ``"always"`` poisons every fit in ``[start, end)``;
    ``"once"`` poisons exactly the ``start`` fit; ``"ramp"`` scales the
    attack linearly from ``1/ramp_rounds`` at ``start`` to full
    strength over ``ramp_rounds`` fits (then holds until ``end``).
    ``std`` of None reads ``Settings.ATTACK_NOISE_STD`` at poison time.
    """

    attack: str = "sign_flip"
    mode: str = "always"
    start: int = 0
    end: Optional[int] = None
    std: Optional[float] = None
    ramp_rounds: int = 1

    def __post_init__(self) -> None:
        if self.attack not in ATTACKS:
            raise ValueError(
                f"Unknown attack {self.attack!r}: expected one of {ATTACKS}"
            )
        if self.mode not in MODES:
            raise ValueError(
                f"Unknown mode {self.mode!r}: expected one of {MODES}"
            )

    def strength(self, round: int) -> float:
        """Attack intensity in [0, 1] for one fit ordinal; 0 = honest."""
        if round < self.start:
            return 0.0
        if self.mode == "once":
            return 1.0 if round == self.start else 0.0
        if self.end is not None and round >= self.end:
            return 0.0
        if self.mode == "ramp":
            ramp = max(1, int(self.ramp_rounds))
            return min(1.0, (round - self.start + 1) / ramp)
        return 1.0

    @property
    def name(self) -> str:
        if self.attack == "additive_noise":
            std = self.std if self.std is not None else Settings.ATTACK_NOISE_STD
            return f"additive_noise(std={std})"
        return self.attack


class AttackPlan:
    """Seeded per-peer attack schedules, keyed by address (or node
    index — resolved when the plan is applied)."""

    def __init__(
        self,
        peers: "dict[Any, AttackSpec] | None" = None,
        seed: Optional[int] = None,
    ) -> None:
        # unguarded: plan config — built once, read-only after
        # construction (the PlannedAdversary wrappers only read).
        self.peers: dict[Any, AttackSpec] = dict(peers or {})
        self._seed = seed

    @property
    def seed(self) -> int:
        """Plan seed (falls back to Settings.SEED at use time, the
        FaultInjector convention)."""
        return (Settings.SEED or 0) if self._seed is None else self._seed

    @classmethod
    def from_dict(cls, spec: dict[str, Any]) -> "AttackPlan":
        peers: dict[Any, AttackSpec] = {}
        for key, s in (spec.get("peers") or {}).items():
            peers[key] = AttackSpec(**s)
        return cls(peers=peers, seed=spec.get("seed"))

    def spec_for(self, addr: str, index: Optional[int] = None) -> Optional[AttackSpec]:
        """The spec targeting ``addr`` (exact address key first, then
        the positional index as int or string)."""
        hit = self.peers.get(addr)
        if hit is None and index is not None:
            hit = self.peers.get(index)
            if hit is None:
                hit = self.peers.get(str(index))
        return hit

    # --- the poison itself (pure function of (seed, peer, round)) ---

    def poison(
        self, addr: str, round: int, spec: AttackSpec, params: Any
    ) -> Any:
        """Apply ``spec`` at full-strength-scaled ``strength(round)`` to
        a parameter pytree. Deterministic per (plan seed, addr, round,
        leaf index): no shared counters, no interleaving sensitivity."""
        alpha = spec.strength(round)
        if alpha <= 0.0:
            return params
        if spec.attack in REPLAY_ATTACKS:
            # Replay modes poison the freshness TAG, not the numbers —
            # PlannedAdversary.shape_contribution carries the attack.
            return params
        import jax
        import jax.numpy as jnp

        if spec.attack == "sign_flip":
            # alpha=1 is the reference negation; a ramped flip walks
            # the parameters through zero toward the mirror image.
            scale = 1.0 - 2.0 * alpha
            return jax.tree_util.tree_map(lambda x: scale * x, params)
        std = spec.std if spec.std is not None else Settings.ATTACK_NOISE_STD
        std = float(std) * alpha
        base = jax.random.PRNGKey(self.seed)
        base = jax.random.fold_in(base, zlib.crc32(addr.encode()) & 0x7FFFFFFF)
        base = jax.random.fold_in(base, int(round))
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for i, leaf in enumerate(leaves):
            k = jax.random.fold_in(base, i)
            noise = jax.random.normal(k, jnp.shape(leaf), jnp.float32)
            out.append(leaf + (std * noise).astype(jnp.asarray(leaf).dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def engine_scales(
        self,
        addrs: "Sequence[str]",
        n_rounds: int,
        start_round: int = 0,
    ) -> Any:
        """Lower this plan's sign-flip schedule into the fused round
        program: a ``[n_rounds, n]`` per-node multiplier array for
        :meth:`tpfl.parallel.engine.FederationEngine.run_rounds`'s
        ``attack_scales`` — ``scale = 1 − 2α`` at each round's
        ``strength()``, exactly :meth:`poison`'s sign-flip lowering, so
        the engine tier's seeded adversary is the same adversary the
        gRPC tier's ``PlannedAdversary`` applies after a fit. Only
        sign-flip specs lower to a multiplicative scale; other attack
        families (additive noise, replay modes) have no in-program
        equivalent here and raise."""
        import numpy as np

        out = np.ones((int(n_rounds), len(addrs)), np.float32)
        for i, addr in enumerate(addrs):
            spec = self.spec_for(addr, i)
            if spec is None:
                continue
            if spec.attack != "sign_flip":
                raise ValueError(
                    "engine_scales lowers sign_flip schedules only, "
                    f"got {spec.attack!r} for {addr!r}"
                )
            for r in range(int(n_rounds)):
                out[r, i] = 1.0 - 2.0 * spec.strength(start_round + r)
        return out

    def adversary_map(
        self, addrs: "Iterable[str] | None" = None
    ) -> dict[str, str]:
        """Ground truth ``{addr: attack name}``. With ``addrs`` (the
        federation's node addresses in index order), integer/string
        index keys resolve to their address; without, only
        address-keyed peers are returned."""
        resolved: dict[str, str] = {}
        addr_list = list(addrs) if addrs is not None else []
        for i, addr in enumerate(addr_list):
            spec = self.spec_for(addr, i)
            if spec is not None:
                resolved[addr] = spec.name
        if addrs is None:
            for key, spec in self.peers.items():
                if isinstance(key, str) and not key.isdigit():
                    resolved[key] = spec.name
        return resolved


class PlannedAdversary(AdversarialLearner):
    """Round-aware model-poisoning adversary driven by an
    :class:`AttackPlan`: every ``fit()`` trains honestly, then applies
    the plan's scheduled attack (if any) for this peer at this fit
    ordinal. The :data:`REPLAY_ATTACKS` modes additionally skip the
    real fit while active (a flooder's edge is being FAST) and rewrite
    the contribution through :meth:`shape_contribution` — the seam
    ``AsyncRoundStage._contribute`` offers every learner. Pure
    delegation otherwise (see AdversarialLearner)."""

    def __init__(self, inner: Any, plan: AttackPlan, index: Optional[int] = None) -> None:
        super().__init__(inner, attack=lambda p: p)
        self._plan = plan
        self._index = index
        # Fit ordinal = round counter: stages call fit() exactly once
        # per round on the learning thread.
        # unguarded: only the learning thread calls fit().
        self._round = 0
        # Replay cache: (params, contributors, num_samples, version) of
        # this peer's FIRST contribution — what the replay modes
        # re-send. Written once at the first shape_contribution call.
        # unguarded: only the learning thread fits/contributes.
        self._replay_cache: "tuple | None" = None

    def _spec(self) -> Optional[AttackSpec]:
        return self._plan.spec_for(self.get_addr(), self._index)

    def fit(self):
        spec = self._spec()
        if (
            spec is not None
            and spec.attack in REPLAY_ATTACKS
            and spec.strength(self._round) > 0.0
            and self._replay_cache is not None
        ):
            # Active replay window with a cached contribution: no real
            # fit at all — the junk re-send is near-instant, which is
            # exactly how it crowds honest arrivals out of the buffer.
            self._round += 1
            params, contributors, num_samples, _v = self._replay_cache
            model = self._inner.get_model().build_copy(
                params=params,
                contributors=list(contributors),
                num_samples=num_samples,
            )
            self._last_fit_model = model
            return model
        model = self._inner.fit()
        rnd, self._round = self._round, self._round + 1
        addr = self.get_addr()
        if spec is not None and spec.strength(rnd) > 0.0:
            model.set_parameters(
                self._plan.poison(addr, rnd, spec, model.get_parameters())
            )
        self._last_fit_model = model
        return model

    def shape_contribution(self, model: Any, version: int) -> "tuple[Any, int]":
        """Async contribution seam (``AsyncRoundStage._contribute``):
        the replay modes substitute the cached first contribution AND
        its original version tag — the receiver sees either an
        implausibly-stale τ (stale_flood) or a version regressing below
        tags this peer already sent (withhold_replay). Honest (and
        non-replay) contributions pass through, caching the first one
        seen."""
        spec = self._spec()
        if spec is None or spec.attack not in REPLAY_ATTACKS:
            return model, version
        # The fit ordinal that produced `model` (fit() already advanced
        # the counter).
        rnd = max(0, self._round - 1)
        if spec.strength(rnd) > 0.0 and self._replay_cache is not None:
            params, contributors, num_samples, v0 = self._replay_cache
            return (
                model.build_copy(
                    params=params,
                    contributors=list(contributors),
                    num_samples=num_samples,
                ),
                int(v0),
            )
        if self._replay_cache is None:
            try:
                contributors = model.get_contributors()
            except ValueError:
                contributors = [self.get_addr()]
            self._replay_cache = (
                model.get_parameters(),
                list(contributors),
                model.get_num_samples(),
                int(version),
            )
        return model, version


def apply_attack_plan(nodes: "list[Any]", plan: AttackPlan) -> dict[str, str]:
    """Wrap every planned peer's learner in a
    :class:`PlannedAdversary` (nodes must not be started yet). Returns
    the resolved ground-truth adversary map."""
    truth: dict[str, str] = {}
    for i, node in enumerate(nodes):
        spec = plan.spec_for(node.addr, i)
        if spec is None:
            continue
        node.learner = PlannedAdversary(node.learner, plan, index=i)
        truth[node.addr] = spec.name
    return truth


class SlowLearner(AdversarialLearner):
    """Trainer-speed chaos: delegates every fit to the wrapped learner,
    then sleeps the :class:`tpfl.communication.faults.TrainerSpeedPlan`
    delay for this address — the fitted PARAMETERS are bit-identical
    to the undelayed learner's (the sleep follows the compute), only
    the federation-visible finish time skews. This is how the async
    tests build a skewed fleet reproducibly."""

    def __init__(self, inner: Any, delay: float) -> None:
        super().__init__(inner, attack=lambda p: p)
        self._delay = float(delay)

    def fit(self):
        import time as _time

        model = self._inner.fit()
        if self._delay > 0:
            _time.sleep(self._delay)
        self._last_fit_model = model
        return model


def apply_speed_plan(nodes: "list[Any]", plan: Any) -> None:
    """Wire a :class:`tpfl.communication.faults.TrainerSpeedPlan` into
    a federation (nodes must not be started yet): every planned node's
    learner is wrapped in a :class:`SlowLearner`, and — when the async
    serialized discipline is active (``Settings.ASYNC_ROUNDS`` +
    ``ASYNC_SERIALIZED``) — every node's aggregator gets its own fork
    of the plan-seeded :class:`~tpfl.communication.faults
    .AsyncSchedule`, so arrival order serializes identically at every
    node and across same-seed runs."""
    from tpfl.communication.faults import AsyncSchedule

    for node in nodes:
        delay = plan.delay_for(node.addr)
        if delay > 0:
            node.learner = SlowLearner(node.learner, delay)
    if Settings.ASYNC_ROUNDS and Settings.ASYNC_SERIALIZED:
        schedule = AsyncSchedule.for_plan(plan)
        for node in nodes:
            node.aggregator.set_async_schedule(schedule.fork())


def apply_chaos(
    nodes: "list[Any]",
    attack_plan: Optional[AttackPlan] = None,
    fault_plan: Optional[Any] = None,
    speed_plan: Optional[Any] = None,
    seed: Optional[int] = None,
) -> "tuple[dict[str, str], Any]":
    """One chaos spec for one federation: malicious peers (attack
    plan), drops/crashes/partitions (fault plan), and skewed trainer
    speeds (speed plan) in one wiring call. Returns
    ``(adversary_map, fault_injector)`` — the injector (or None) is
    attached to every node's protocol and its schedule clock started.
    """
    truth: dict[str, str] = {}
    if attack_plan is not None:
        truth = apply_attack_plan(nodes, attack_plan)
    if speed_plan is not None:
        apply_speed_plan(nodes, speed_plan)
    injector = None
    if fault_plan is not None:
        from tpfl.communication.faults import FaultInjector

        injector = FaultInjector(fault_plan, seed=seed)
        for node in nodes:
            injector.attach(node.communication)
        injector.start()
    return truth, injector
