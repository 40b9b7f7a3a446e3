"""Learning-plane observatory: contribution ledger, convergence
monitors, attack-signature anomaly detection.

PR-5 made the NETWORK plane observable (which bytes crossed which hop)
and PR-6 the DEVICE plane (what the compiler and chips did with them).
The one plane still dark was the MODEL UPDATES themselves — the thing
the fork's research contribution (adversarial robustness, ``tpfl/
attacks``) actually attacks. This module records, per contribution
folded into any aggregator:

- **update L2 norm** and a **per-leaf norm profile** of the update
  (``contribution - round-start global model``),
- **cosine similarity** to the round-start reference AND to the
  running mean of this round's updates so far,
- FL weight / sample count, the round ordinal, and the PR-5 trace id
  of the payload that carried it,

computed **on-device in one fused jitted reduction per contribution**
(O(1) memory — a donated running-sum accumulator, the PR-3 pattern;
recorded at intake, reduced at the round boundary so the device queue
stays the fit programs' mid-round), landing in a bounded per-node
:class:`ContributionLedger` ring,
``tpfl_contrib_*`` histograms/counters in ``logger.metrics``, and
``contrib``/``anomaly`` records in the flight-recorder ring (which the
existing crash/stop dumps — and ``tools/traceview.py --ledger`` — pick
up automatically, joined to the payload's hop timeline by trace id).

On top of the ledger:

- :class:`ConvergenceMonitor` — per-round global-model delta norm and
  loss-trajectory slope, ``tpfl_convergence_*`` gauges, and
  ``divergence`` / ``plateau`` flight events when the trajectory turns.
- :class:`AnomalyScorer` — deterministic attack-signature detection:
  robust z-score of the update norm against the ledger's running
  median/MAD plus the reference-cosine test. Sign-flip contributions
  show ``cos_ref ≈ -1`` (the whole model is negated relative to the
  shared round-start point); additive-noise contributions show update
  norms tens of robust sigmas above the honest cluster. Detection is
  **observational** — flags never change aggregation results;
  quarantine is a future robust-aggregation concern.

Determinism: per-entry features are pure functions of (contribution
params, round-start reference), both of which are seed-deterministic,
so :meth:`ContributionLedger.detections` — which dedups
single-contributor entries by (peer, round) and scores them against a
deduped global baseline — produces byte-identical flags across
same-seed runs regardless of gossip arrival order
(``tests/test_ledger.py::test_detections_dedup_across_observers``).
The per-observer flags recorded live at intake use
the observer's own running window and are near-identical in practice
but not guaranteed byte-stable; the deterministic view is the verdict
surface.

Gating (the PR-6 discipline): every entry point checks
``Settings.LEDGER_ENABLED`` first — disabled, the ledger is one
attribute read per call site and adds ZERO device dispatches
(``tests/test_ledger.py::test_disabled_ledger_adds_zero_dispatches``).
jax is imported
lazily so the management layer stays backend-free.

Concurrency: ring/state sit under one ``make_lock`` leaf lock; the
jitted stat reduction runs under it (jax takes no tpfl locks, so no
lock-order edges form), but registry/flight emission happens OUTSIDE
the lock — telemetry never extends another subsystem's critical
section.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Optional

from tpfl.concurrency import make_lock
from tpfl.management.telemetry import flight, metrics
from tpfl.settings import Settings

#: Update L2 norms span tiny fine-tune deltas to whole-model-scale
#: poison; log-ish buckets keep the histogram readable at both ends.
NORM_BUCKETS: tuple[float, ...] = (
    0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0, 300.0, 1000.0,
)

#: Cosine similarity buckets over [-1, 1].
COSINE_BUCKETS: tuple[float, ...] = (
    -0.8, -0.6, -0.4, -0.2, 0.0, 0.2, 0.4, 0.6, 0.8, 1.0,
)

#: MAD floor as a fraction of the median: a perfectly tight honest
#: cluster (identical seeded fits) must not make every later entry an
#: infinite-z outlier.
_MAD_REL_FLOOR = 0.05
_EPS = 1e-12

#: builtin alias — several observatory APIs take a ``round`` kwarg for
#: consistency with the stage/profiler surfaces, shadowing the builtin
#: in those scopes (same convention as ``profiling.round_``).
_round = round


def enabled() -> bool:
    return bool(Settings.LEDGER_ENABLED)


def active() -> bool:
    """True when the ledger's round state must be maintained: either
    the observational knob (LEDGER_ENABLED) or the active defense
    (QUARANTINE_ENABLED — quarantine verdicts are ledger scores, so the
    engine needs open-round references and scored windows even when the
    passive record path is off)."""
    return bool(Settings.LEDGER_ENABLED or Settings.QUARANTINE_ENABLED)


# --- fused on-device contribution stats -----------------------------------
#
# One jitted reduction per recorded contribution: update norm, per-leaf
# norm profile, cosine vs the round-start reference, cosine vs the
# running mean of this round's updates, and the folded running-sum
# accumulator (donated — O(1) memory in the contribution count, the
# PR-3 accumulator pattern). Built lazily on first enabled use so
# importing the management layer never drags a jax backend in.

_stat_fns: "list[tuple[Callable, Callable]]" = []  # 0- or 1-element


def _build_stat_fns() -> "tuple[Callable, Callable]":
    import jax
    import jax.numpy as jnp
    from functools import partial

    def _core(params, ref, mean_acc, n):
        f32 = jnp.float32
        upd = jax.tree_util.tree_map(
            lambda p, r: (p.astype(f32) - r.astype(f32)), params, ref
        )
        leaf_sq = jnp.stack(
            [jnp.sum(u * u) for u in jax.tree_util.tree_leaves(upd)]
        )
        upd_sq = jnp.sum(leaf_sq)
        p_sq = sum(
            jnp.sum(p.astype(f32) ** 2)
            for p in jax.tree_util.tree_leaves(params)
        )
        r_sq = sum(
            jnp.sum(r.astype(f32) ** 2)
            for r in jax.tree_util.tree_leaves(ref)
        )
        pr_dot = sum(
            jnp.sum(p.astype(f32) * r.astype(f32))
            for p, r in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(ref),
            )
        )
        cos_ref = pr_dot / jnp.sqrt(jnp.maximum(p_sq * r_sq, _EPS))
        # Cosine vs the running MEAN of prior updates (mean = acc / n;
        # cosine is scale-invariant so the sum stands in for the mean).
        um_dot = sum(
            jnp.sum(u * a)
            for u, a in zip(
                jax.tree_util.tree_leaves(upd),
                jax.tree_util.tree_leaves(mean_acc),
            )
        )
        m_sq = sum(
            jnp.sum(a * a) for a in jax.tree_util.tree_leaves(mean_acc)
        )
        cos_mean = jnp.where(
            n > 0, um_dot / jnp.sqrt(jnp.maximum(upd_sq * m_sq, _EPS)), 0.0
        )
        new_acc = jax.tree_util.tree_map(jnp.add, mean_acc, upd)
        scalars = jnp.stack(
            [
                jnp.sqrt(upd_sq),
                jnp.sqrt(jnp.maximum(r_sq, 0.0)),
                cos_ref,
                cos_mean,
            ]
        )
        return scalars, jnp.sqrt(leaf_sq), new_acc

    @jax.jit
    def first(params, ref):
        f32 = jnp.float32
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), f32), params
        )
        return _core(params, ref, zeros, jnp.int32(0))

    @partial(jax.jit, donate_argnums=(2,))
    def update(params, ref, mean_acc, n):
        return _core(params, ref, mean_acc, n)

    return first, update


def _stats(params: Any, ref: Any, acc: Any, n: int):
    """(scalars, per-leaf norms, new running-sum acc) — dispatches the
    fused reduction, building/caching the jitted pair on first use."""
    if not _stat_fns:
        _stat_fns.append(_build_stat_fns())
    first, update = _stat_fns[0]
    if acc is None or n <= 0:
        return first(params, ref)
    return update(params, ref, acc, n)


# --- anomaly scoring ------------------------------------------------------


def robust_z(value: float, window: "list[float]") -> float:
    """Robust z-score of ``value`` against ``window``'s median/MAD
    (1.4826·MAD ≈ sigma for normal data; MAD floored at
    ``_MAD_REL_FLOOR``·median so a degenerate tight cluster can't make
    every newcomer an infinite outlier)."""
    if not window:
        return 0.0
    xs = sorted(window)
    mid = len(xs) // 2
    med = xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])
    mad = sorted(abs(x - med) for x in xs)
    madv = mad[mid] if len(mad) % 2 else 0.5 * (mad[mid - 1] + mad[mid])
    sigma = max(1.4826 * madv, _MAD_REL_FLOOR * abs(med), _EPS)
    return (value - med) / sigma


class AnomalyScorer:
    """Attack-signature scoring — a pure function of (entry features,
    norm baseline window), so the same entry always scores the same.

    Two tests, each targeting one of the harness's attack families
    (``tpfl/attacks/attacks.py``):

    - **sign-flip**: ``cos_ref ≤ Settings.LEDGER_ANOMALY_COS``. A
      sign-flipped contribution is ``-(ref + δ)`` — its cosine against
      the shared round-start reference sits at ≈ -1 while honest
      contributions sit at ≈ +1; no history needed, so round 0 already
      flags.
    - **norm outlier** (additive noise): robust z-score of the update
      L2 norm against the window's median/MAD ``≥
      Settings.LEDGER_ANOMALY_Z``, once the window holds
      ``Settings.LEDGER_ANOMALY_MIN_N`` honest-majority samples.
      ``N(0, std)`` noise over d parameters adds ``std·√d`` of update
      norm — tens of robust sigmas above the honest cluster at the
      harness defaults.
    - **stale flood** (async buffered rounds — the
      ``tpfl/attacks/plan.py`` ``stale_flood`` / ``withhold_replay``
      signatures): a contribution whose staleness ``τ`` exceeds
      ``Settings.ASYNC_STALENESS_MAX`` (implausibly stale — honest
      stragglers sit at single-digit τ) or whose version ordinal
      REGRESSES below one the same peer already contributed (a peer's
      start version is monotonic by construction: it only advances as
      aggregates are adopted — regression means a replayed old
      contribution). Needs no norm baseline, so a flooder is flagged
      the moment its τ crosses the bound. Disabled when
      ``ASYNC_STALENESS_MAX`` is negative; sync rounds (τ = 0
      everywhere, versions = rounds) never trip it.
    """

    @staticmethod
    def score(
        update_norm: float,
        cos_ref: float,
        window: "list[float]",
        staleness: int = 0,
        version_regressed: bool = False,
    ) -> "tuple[bool, list[str], float]":
        """(flagged, reasons, z_norm)."""
        reasons: list[str] = []
        if cos_ref <= float(Settings.LEDGER_ANOMALY_COS):
            reasons.append("sign_flip")
        z = robust_z(update_norm, window)
        if (
            len(window) >= max(1, int(Settings.LEDGER_ANOMALY_MIN_N))
            and z >= float(Settings.LEDGER_ANOMALY_Z)
        ):
            reasons.append("norm_outlier")
        max_tau = int(Settings.ASYNC_STALENESS_MAX)
        if max_tau >= 0 and (int(staleness) > max_tau or version_regressed):
            reasons.append("stale_flood")
        return bool(reasons), reasons, z


# --- contribution ledger --------------------------------------------------


class ContributionLedger:
    """Bounded per-node ring of contribution records + per-round
    running-mean accumulators.

    Lifecycle (wired by the aggregator/stages seams):

    - ``open_round(node, round, ref_params)`` — TrainStage, right after
      ``set_nodes_to_aggregate``: pins the round ordinal and the
      round-start global parameters every contribution is measured
      against.
    - ``record(node, model, trace)`` — ``Aggregator.add_model``, after
      the intake checks accept a contribution and BEFORE it folds: the
      fused stats dispatch (ENQUEUE only — see below) + ring append.
    - ``close_round(node)`` — ``Aggregator.clear``: materializes the
      round's pending entries, then drops the reference/accumulator
      (the ring persists across rounds — it IS the anomaly baseline).

    Intake is pure Python by design: mid-round, the device queue
    belongs to the fit/fold programs, and both dispatching the stat
    reduction and syncing its result there cost ~5-20x their quiet-
    queue price on a saturated host (measured ~7 ms per record vs ~1 ms
    idle). ``record`` therefore only parks a reference to the
    contribution's immutable parameter pytree (the aggregator holds the
    same arrays until round close — no added footprint), and
    :meth:`flush` runs the fused reductions, scoring and emission at
    round close (or at the first query/scrape), when the device is
    idle. Entry dicts are mutated in place, so a reference returned by
    ``record`` is complete after any flushing call.
    """

    def __init__(self) -> None:
        self._lock = make_lock("ContributionLedger._lock")
        # guarded-by: _lock
        self._rings: dict[str, deque] = {}
        # Per-node open-round state: {"round", "ref", "acc", "n"}.
        # guarded-by: _lock
        self._open: dict[str, dict] = {}
        # Cross-observer verdict cache for the active-defense path
        # (score_now): a contribution's stats are a pure function of
        # (params, round-start reference), and in-process federations
        # share numerically identical references — so the fused
        # reduction runs ONCE per (peer, round) process-wide and every
        # other observer reuses the scalars
        # (tests/test_quarantine.py::test_repush_scores_once): without
        # it, N co-located observers each paid a mid-round
        # dispatch+sync per contribution. Bounded FIFO (_score_keys).
        # guarded-by: _lock
        self._score_cache: dict[tuple, dict] = {}
        # guarded-by: _lock
        self._score_keys: deque = deque()
        # Per-node last-opened round: rounds only advance within one
        # experiment, so a node re-opening a round it already saw means
        # a NEW experiment reuses the same (peer, round) keys — the
        # verdict cache must drop (stale scalars were computed against
        # the previous experiment's reference).
        # guarded-by: _lock
        self._last_open: dict[str, int] = {}
        # Per-(observer node, peer) max version ordinal seen — the
        # version-REGRESSION baseline of the stale_flood signature
        # (a peer's start version is monotonic by construction, so a
        # lower tag than one it already contributed is a replay).
        # Observer-independent in value: the version reconstructs the
        # contribution's own start ordinal. Cleared with the score
        # cache on experiment restart.
        # guarded-by: _lock
        self._peer_version: dict[tuple, int] = {}

    # --- lifecycle ---

    def open_round(self, node: str, round: "int | None", ref_params: Any) -> None:
        if not active():
            return
        with self._lock:
            rnd = int(round) if round is not None else -1
            if rnd <= self._last_open.get(node, -1):
                self._score_cache.clear()
                self._score_keys.clear()
                self._last_open.clear()
                self._peer_version.clear()
            self._last_open[node] = rnd
            self._open[node] = {
                "round": rnd,
                "ref": ref_params,
                "acc": None,
                "n": 0,
            }

    def close_round(self, node: str) -> None:
        # Materialize the round's pending stats now — the fit/fold
        # programs have drained, so the syncs are cheap — then drop the
        # reference/accumulator. Unconditional: a round opened while
        # LEDGER_ENABLED must release its pinned params even if the
        # knob was flipped off mid-round.
        self.flush(node)
        with self._lock:
            self._open.pop(node, None)

    def record(
        self, node: str, model: Any, trace: str = "", staleness: int = 0
    ) -> "dict | None":
        """Record one accepted contribution; returns the ledger entry
        (or None when disabled / no round is open on ``node``).

        ``staleness``: async buffered rounds' version-distance ordinal
        (0 for sync rounds). Rides the entry as ``staleness`` plus the
        derived ``version`` (= fold round − staleness, the model
        version the update was trained FROM) so detection windows and
        traceview joins stay keyed per-version, not per-wall-clock.

        Single-contributor models get the full fused on-device stat
        reduction + anomaly scoring. Multi-contributor PARTIAL
        aggregates get a metadata-only entry (peer set, round, weight,
        trace — no device work): they are diluted mixtures the scorer
        ignores by design, every raw update is guaranteed a single
        record at its own trainer's intake, and on a saturated host the
        extra dispatches were the bulk of the enabled tax for zero
        detection signal."""
        if not Settings.LEDGER_ENABLED:
            return None
        try:
            contributors = sorted(model.get_contributors())
        except Exception:
            return None
        if len(contributors) > 1:
            return self._record_partial(node, model, contributors, trace)
        import numpy as np

        with self._lock:
            st = self._open.get(node)
            if st is None:
                return None
            # Intake is PURE PYTHON: park a reference to the
            # contribution's (immutable) parameter pytree; the fused
            # reduction runs at flush() when the device queue is quiet.
            # The aggregator holds these same arrays until round close
            # anyway, so the pending reference adds no footprint.
            entry = {
                "node": node,
                "peer": "+".join(contributors),
                "contributors": contributors,
                "single": True,
                "round": st["round"],
                "staleness": int(staleness),
                "version": st["round"] - int(staleness),
                "num_samples": int(model.get_num_samples()),
                "update_norm": None,
                "ref_norm": None,
                "cos_ref": None,
                "cos_mean": None,
                "leaf_norms": [],
                "trace": trace,
                "t": time.monotonic(),
                "z_norm": 0.0,
                "flagged": False,
                "reasons": [],
                "quarantined": False,
                "_params": model.get_parameters(),
            }
            ring = self._rings.get(node)
            if ring is None:
                ring = self._rings[node] = deque(
                    maxlen=max(1, int(Settings.LEDGER_RING))
                )
            ring.append(entry)
        return entry

    def score_now(
        self, node: str, model: Any, trace: str = "", staleness: int = 0
    ) -> "dict | None":
        """Eagerly record AND score one single-contributor contribution
        at intake — the active-defense path (tpfl.management.quarantine
        needs the verdict BEFORE the aggregator folds, so the parked
        flush-at-close discipline of :meth:`record` does not apply
        here; the dispatch+sync tax mid-round is the defense's price,
        not measured on the chip).

        Deduped by (peer, round) per observer: gossip re-pushes of the
        same contribution return the already-scored entry without
        re-scoring or re-emitting. The norm-outlier window is the
        observer's PRIOR rounds' clean (unflagged) single entries —
        complete by the time a round opens, so the verdict is a pure
        function of seed-deterministic state, not of this round's
        arrival order. Returns the scored entry, or None when no round
        is open / the model is not single-contributor / defenses are
        off."""
        if not active():
            return None
        try:
            contributors = sorted(model.get_contributors())
        except Exception:
            return None
        if len(contributors) != 1:
            return None
        import numpy as np

        peer = contributors[0]
        with self._lock:
            st = self._open.get(node)
            if st is None:
                return None
            ring = self._rings.get(node)
            if ring is None:
                ring = self._rings[node] = deque(
                    maxlen=max(1, int(Settings.LEDGER_RING))
                )
            for e in reversed(ring):
                if (
                    e["single"]
                    and e["peer"] == peer
                    and e["round"] == st["round"]
                    and e["update_norm"] is not None
                ):
                    return e  # re-push of an already-scored contribution
            # Version-regression check BEFORE the watermark updates:
            # the contribution's own start ordinal (round − τ, observer-
            # independent) against the max this observer has seen from
            # the peer — a lower tag is a replayed old contribution
            # (the withhold_replay signature).
            version = st["round"] - int(staleness)
            vkey = (node, peer)
            prev_version = self._peer_version.get(vkey)
            regressed = prev_version is not None and version < prev_version
            self._peer_version[vkey] = (
                version if prev_version is None else max(prev_version, version)
            )
            cached = self._score_cache.get((peer, st["round"]))
            if cached is not None:
                # Another observer already ran this contribution's
                # reduction: reuse the scalars AND the verdict (pure
                # functions of seed-deterministic state — identical
                # here by construction, and uniformity across
                # observers is exactly what the exclusion protocol
                # relies on). Zero added device work.
                scored = dict(cached)
            else:
                # Per-VERSION window (async staleness discipline): the
                # norm baseline is prior clean entries from EARLIER
                # model versions than the one this update trained from.
                # Sync rounds have staleness 0 everywhere, so version
                # == round and this is bit-identical to the historical
                # prior-rounds filter.
                window = [
                    x["update_norm"]
                    for x in ring
                    if x["single"]
                    and x["update_norm"] is not None
                    and x.get("version", x["round"]) < version
                    and not x["flagged"]
                ]
                scalars_dev, leaf_dev, new_acc = _stats(
                    model.get_parameters(), st["ref"], st["acc"], st["n"]
                )
                had_prior = st["n"] > 0
                st["acc"] = new_acc
                st["n"] += 1
                scalars = np.asarray(scalars_dev, np.float64)
                update_norm = float(scalars[0])
                flagged, reasons, z_norm = AnomalyScorer.score(
                    update_norm, float(scalars[2]), window,
                    staleness=staleness, version_regressed=regressed,
                )
                scored = {
                    "update_norm": update_norm,
                    "ref_norm": float(scalars[1]),
                    "cos_ref": float(scalars[2]),
                    "cos_mean": float(scalars[3]) if had_prior else None,
                    "leaf_norms": [
                        _round(float(x), 6)
                        for x in np.asarray(leaf_dev, np.float64)
                    ],
                    "z_norm": _round(z_norm, 4),
                    "flagged": flagged,
                    "reasons": list(reasons),
                }
                self._score_cache[(peer, st["round"])] = dict(scored)
                self._score_keys.append((peer, st["round"]))
                while len(self._score_keys) > 2048:
                    self._score_cache.pop(self._score_keys.popleft(), None)
            entry = {
                "node": node,
                "peer": peer,
                "contributors": contributors,
                "single": True,
                "round": st["round"],
                "staleness": int(staleness),
                "version": st["round"] - int(staleness),
                "num_samples": int(model.get_num_samples()),
                "trace": trace,
                "t": time.monotonic(),
                "quarantined": False,
                **scored,
            }
            entry["reasons"] = list(entry["reasons"])
            ring.append(entry)
        self._emit(entry)  # OUTSIDE _lock
        return entry

    def record_external(
        self,
        node: str,
        peer: str,
        round: "int | None",
        update_norm: float,
        cos_ref: float,
        num_samples: int = 1,
        trace: str = "",
        staleness: int = 0,
    ) -> "dict | None":
        """Score-and-record one contribution whose statistics were
        already computed elsewhere — the engine plane's fan-out
        (``tpfl.management.engine_obs``): the fused round program's
        telemetry carry holds each node's update norm and reference
        cosine, so the entry needs NO open round, no pinned reference
        params and zero device work here. Scored against this observer
        ring's prior clean window through the same
        :class:`AnomalyScorer` thresholds as the gRPC-tier intake, and
        emitted identically (``tpfl_contrib_*`` metrics, ``contrib`` /
        ``anomaly`` flight events) — so :meth:`detections` and
        ``tpfl.management.quarantine.replay_decisions`` judge
        engine-tier contributions exactly like protocol-tier ones.
        Deduped by (peer, round) per observer: a replayed window
        returns the existing entry."""
        if not active():
            return None
        rnd = int(round) if round is not None else -1
        version = rnd - int(staleness)
        with self._lock:
            ring = self._rings.get(node)
            if ring is None:
                ring = self._rings[node] = deque(
                    maxlen=max(1, int(Settings.LEDGER_RING))
                )
            for e in reversed(ring):
                if (
                    e["single"]
                    and e["peer"] == peer
                    and e["round"] == rnd
                    and e["update_norm"] is not None
                ):
                    return e
            vkey = (node, peer)
            prev_version = self._peer_version.get(vkey)
            regressed = prev_version is not None and version < prev_version
            self._peer_version[vkey] = (
                version if prev_version is None else max(prev_version, version)
            )
            window = [
                x["update_norm"]
                for x in ring
                if x["single"]
                and x["update_norm"] is not None
                and x.get("version", x["round"]) < version
                and not x["flagged"]
            ]
            flagged, reasons, z_norm = AnomalyScorer.score(
                float(update_norm), float(cos_ref), window,
                staleness=staleness, version_regressed=regressed,
            )
            entry = {
                "node": node,
                "peer": peer,
                "contributors": [peer],
                "single": True,
                "round": rnd,
                "staleness": int(staleness),
                "version": version,
                "num_samples": int(num_samples),
                "update_norm": float(update_norm),
                "ref_norm": None,
                "cos_ref": float(cos_ref),
                "cos_mean": None,
                "leaf_norms": [],
                "trace": trace,
                "t": time.monotonic(),
                "z_norm": _round(z_norm, 4),
                "flagged": flagged,
                "reasons": list(reasons),
                "quarantined": False,
            }
            ring.append(entry)
        self._emit(entry)  # OUTSIDE _lock
        return entry

    def flush(self, node: Optional[str] = None) -> None:
        """Materialize pending entries: run each parked contribution's
        fused reduction (in ring order — the donated running-mean
        accumulator chain is sequential per node), score it against the
        preceding window, and emit metrics/flight records. Called by
        ``close_round`` and by every query surface; idempotent, cheap
        when nothing is pending."""
        import numpy as np

        to_emit: list[dict] = []
        with self._lock:
            rings = (
                [self._rings[node]]
                if node is not None and node in self._rings
                else list(self._rings.values())
            )
            for ring in rings:
                window: "list[float] | None" = None
                # Ring-order version watermark per peer: the regression
                # half of the stale_flood signature for the passive
                # (flush-at-close) path.
                seen_version: dict[str, int] = {}
                for e in ring:
                    params = e.pop("_params", None)
                    version = e.get("version")
                    prev_v = (
                        seen_version.get(e["peer"])
                        if e.get("single")
                        else None
                    )
                    if e.get("single") and version is not None:
                        seen_version[e["peer"]] = (
                            version
                            if prev_v is None
                            else max(prev_v, version)
                        )
                    if params is None:
                        continue
                    st = self._open.get(e["node"])
                    if st is None or st["round"] != e["round"]:
                        # Round state already gone (reset mid-round /
                        # knob flip): keep the metadata, skip the stats.
                        continue
                    if window is None:  # lazily: only rings with work
                        window = [
                            x["update_norm"]
                            for x in ring
                            if x["single"] and x["update_norm"] is not None
                        ]
                    scalars_dev, leaf_dev, new_acc = _stats(
                        params, st["ref"], st["acc"], st["n"]
                    )
                    had_prior = st["n"] > 0
                    st["acc"] = new_acc
                    st["n"] += 1
                    scalars = np.asarray(scalars_dev, np.float64)
                    e["update_norm"] = float(scalars[0])
                    e["ref_norm"] = float(scalars[1])
                    e["cos_ref"] = float(scalars[2])
                    e["cos_mean"] = float(scalars[3]) if had_prior else None
                    e["leaf_norms"] = [
                        round(float(x), 6)
                        for x in np.asarray(leaf_dev, np.float64)
                    ]
                    flagged, reasons, z_norm = AnomalyScorer.score(
                        e["update_norm"], e["cos_ref"], window,
                        staleness=e.get("staleness", 0),
                        version_regressed=bool(
                            prev_v is not None
                            and version is not None
                            and version < prev_v
                        ),
                    )
                    e["z_norm"] = round(z_norm, 4)
                    e["flagged"] = flagged
                    e["reasons"] = reasons
                    window.append(e["update_norm"])
                    to_emit.append(e)
        for e in to_emit:  # OUTSIDE _lock, in ring order
            self._emit(e)

    def _record_partial(
        self, node: str, model: Any, contributors: list[str], trace: str
    ) -> "dict | None":
        """Metadata-only ledger entry for a multi-contributor partial
        aggregate: who it bundled, when, with what weight — zero device
        dispatches and never scored."""
        with self._lock:
            st = self._open.get(node)
            if st is None:
                return None
            entry = {
                "node": node,
                "peer": "+".join(contributors),
                "contributors": contributors,
                "single": False,
                "round": st["round"],
                "num_samples": int(model.get_num_samples()),
                "update_norm": None,
                "ref_norm": None,
                "cos_ref": None,
                "cos_mean": None,
                "leaf_norms": [],
                "trace": trace,
                "t": time.monotonic(),
                "z_norm": 0.0,
                "flagged": False,
                "reasons": [],
                "quarantined": False,
            }
            ring = self._rings.get(node)
            if ring is None:
                ring = self._rings[node] = deque(
                    maxlen=max(1, int(Settings.LEDGER_RING))
                )
            ring.append(entry)
        metrics.counter("tpfl_contrib_total", labels={"node": node})
        flight.record(
            node,
            {
                "kind": "event",
                "name": "contrib",
                "node": node,
                "trace": trace,
                "t": entry["t"],
                "peer": entry["peer"],
                "round": entry["round"],
                "num_samples": entry["num_samples"],
                "flagged": False,
            },
        )
        return entry

    def _emit(self, entry: dict) -> None:
        """Registry + flight emission — OUTSIDE ``_lock``."""
        node = entry["node"]
        labels = {"node": node}
        metrics.counter("tpfl_contrib_total", labels=labels)
        metrics.observe(
            "tpfl_contrib_update_norm", entry["update_norm"],
            labels=labels, buckets=NORM_BUCKETS,
        )
        metrics.observe(
            "tpfl_contrib_cosine", entry["cos_ref"],
            labels=labels, buckets=COSINE_BUCKETS,
        )
        metrics.gauge(
            "tpfl_contrib_last_z", entry["z_norm"], labels=labels
        )
        flight.record(
            node,
            {
                "kind": "event",
                "name": "contrib",
                "node": node,
                "trace": entry["trace"],
                "t": entry["t"],
                "peer": entry["peer"],
                "round": entry["round"],
                "update_norm": round(entry["update_norm"], 6),
                "cos_ref": round(entry["cos_ref"], 6),
                "num_samples": entry["num_samples"],
                "flagged": entry["flagged"],
            },
        )
        if entry["flagged"]:
            for reason in entry["reasons"]:
                metrics.counter(
                    "tpfl_contrib_flagged_total",
                    labels={"node": node, "reason": reason},
                )
            flight.record(
                node,
                {
                    "kind": "event",
                    "name": "anomaly",
                    "node": node,
                    "trace": entry["trace"],
                    "t": entry["t"],
                    "peer": entry["peer"],
                    "round": entry["round"],
                    "reasons": ",".join(entry["reasons"]),
                    "z_norm": entry["z_norm"],
                    "cos_ref": round(entry["cos_ref"], 6),
                },
            )
            from tpfl.management.logger import logger

            logger.warning(
                node,
                f"Anomalous contribution from {entry['peer']} (round "
                f"{entry['round']}): {','.join(entry['reasons'])} "
                f"(|u|={entry['update_norm']:.3g}, z={entry['z_norm']:.1f}, "
                f"cos_ref={entry['cos_ref']:.3f})",
            )

    # --- query surface ---

    def entries(self, node: Optional[str] = None) -> list[dict]:
        self.flush(node)
        with self._lock:
            if node is not None:
                return [dict(e) for e in self._rings.get(node, ())]
            return [
                dict(e)
                for n in sorted(self._rings)
                for e in self._rings[n]
            ]

    def stats_for(self, node: str) -> dict:
        """{entries, flagged} — the node-monitor gauge surface."""
        self.flush(node)
        with self._lock:
            ring = self._rings.get(node, ())
            return {
                "entries": len(ring),
                "flagged": sum(1 for e in ring if e["flagged"]),
            }

    def detections(self) -> dict:
        """Deterministic global detection verdict.

        Single-contributor entries are deduped by (peer, round) — their
        features are pure functions of seed-deterministic state, so
        whichever observer recorded one, the numbers agree — then every
        deduped entry is scored against the deduped norm baseline
        (median/MAD over ALL deduped entries: the honest majority
        dominates at ≤~40% adversaries). Returns::

            {"entries": [...sorted...],
             "flagged": {peer: {"rounds": [...], "reasons": [...]}},
             "peers": [every peer seen]}

        Byte-identical for identical inputs
        (``tests/test_ledger.py::test_detections_dedup_across_observers``).
        """
        self.flush()
        with self._lock:
            # update_norm None = stats skipped (round state was gone by
            # flush time) — nothing to score.
            all_entries = [
                e
                for ring in self._rings.values()
                for e in ring
                if e["single"] and e["update_norm"] is not None
            ]
        dedup: dict[tuple, dict] = {}
        for e in all_entries:
            dedup.setdefault((e["peer"], e["round"]), e)
        baseline = [e["update_norm"] for e in dedup.values()]
        flagged: dict[str, dict] = {}
        scored = []
        # Per-peer version watermark over the (peer, round)-sorted
        # walk: within a peer, rounds ascend, so "max version at any
        # EARLIER round" is a running max — deterministic regardless
        # of which observers recorded which entry.
        max_version: dict[str, int] = {}
        for (peer, rnd) in sorted(dedup):
            e = dedup[(peer, rnd)]
            window = [x for x in baseline]
            version = e.get("version", rnd)
            prev_v = max_version.get(peer)
            max_version[peer] = (
                version if prev_v is None else max(prev_v, version)
            )
            is_flagged, reasons, z = AnomalyScorer.score(
                e["update_norm"], e["cos_ref"], window,
                staleness=e.get("staleness", 0),
                version_regressed=bool(
                    prev_v is not None and version < prev_v
                ),
            )
            scored.append(
                {
                    "peer": peer,
                    "round": rnd,
                    "update_norm": round(e["update_norm"], 6),
                    "cos_ref": round(e["cos_ref"], 6),
                    "staleness": int(e.get("staleness", 0)),
                    "version": int(version),
                    "z_norm": round(z, 4),
                    "flagged": is_flagged,
                    "reasons": reasons,
                }
            )
            if is_flagged:
                rec = flagged.setdefault(peer, {"rounds": [], "reasons": []})
                rec["rounds"].append(rnd)
                for r in reasons:
                    if r not in rec["reasons"]:
                        rec["reasons"].append(r)
        return {
            "entries": scored,
            "flagged": {k: flagged[k] for k in sorted(flagged)},
            "peers": sorted({e["peer"] for e in dedup.values()}),
        }

    def reset(self) -> None:
        with self._lock:
            self._rings.clear()
            self._open.clear()
            self._score_cache.clear()
            self._score_keys.clear()
            self._last_open.clear()
            self._peer_version.clear()


# --- convergence monitor --------------------------------------------------


_norm_fns: "list[Callable]" = []  # 0- or 1-element


def _delta_norm(params: Any, prev: Any) -> "tuple[float, float]":
    """(||params - prev||₂, ||params||₂) in one fused jitted dispatch."""
    if not _norm_fns:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(p, q):
            f32 = jnp.float32
            d_sq = sum(
                jnp.sum((a.astype(f32) - b.astype(f32)) ** 2)
                for a, b in zip(
                    jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(q),
                )
            )
            p_sq = sum(
                jnp.sum(a.astype(f32) ** 2)
                for a in jax.tree_util.tree_leaves(p)
            )
            return jnp.stack([jnp.sqrt(d_sq), jnp.sqrt(p_sq)])

        _norm_fns.append(fn)
    import numpy as np

    out = np.asarray(_norm_fns[0](params, prev), np.float64)
    return float(out[0]), float(out[1])


class ConvergenceMonitor:
    """Is the federation converging? Two per-round signals:

    - **global-model delta norm** — ``||x_r - x_{r-1}||`` (and its
      ratio to ``||x_r||``), observed where every node adopts the
      round result (RoundFinishedStage). A plateau (relative delta ~ 0
      over the window) or divergence (delta growing monotonically over
      the window) raises a flight event + counter.
    - **loss-trajectory slope** — least-squares slope of the trailing
      ``Settings.LEDGER_CONVERGENCE_WINDOW`` per-fit train losses
      (JaxLearner.fit's tap — one already-synced host float, no added
      device work). A full window of strictly-rising losses raises
      ``divergence``.
    """

    #: Relative delta below which a round counts toward a plateau.
    PLATEAU_REL = 1e-4

    def __init__(self) -> None:
        self._lock = make_lock("ConvergenceMonitor._lock")
        # guarded-by: _lock
        self._prev: dict[str, Any] = {}
        # guarded-by: _lock
        self._deltas: dict[str, deque] = {}
        # guarded-by: _lock
        self._losses: dict[str, deque] = {}

    def _window(self) -> int:
        return max(2, int(Settings.LEDGER_CONVERGENCE_WINDOW))

    def observe_global(
        self, node: str, round: "int | None", params: Any
    ) -> "dict | None":
        if not Settings.LEDGER_ENABLED:
            return None
        with self._lock:
            prev = self._prev.get(node)
            self._prev[node] = params
        if prev is None:
            return None
        try:
            delta, norm = _delta_norm(params, prev)
        except Exception:
            # Structure changed mid-run (model swap): restart the series.
            return None
        return self.observe_delta(node, round, delta, norm)

    def observe_delta(
        self, node: str, round: "int | None", delta: float, norm: float
    ) -> "dict | None":
        """The plateau/divergence logic over a PRECOMPUTED
        ``(||x_r − x_{r−1}||, ||x_r||)`` pair — the engine plane's
        entry point (the fused round program's telemetry carry already
        holds both, so the fan-out adds no device work);
        :meth:`observe_global` routes here after its own fused
        dispatch."""
        if not Settings.LEDGER_ENABLED:
            return None
        rnd = int(round) if round is not None else -1
        delta, norm = float(delta), float(norm)
        rel = delta / max(norm, _EPS)
        w = self._window()
        with self._lock:
            dq = self._deltas.setdefault(node, deque(maxlen=w))
            dq.append(delta)
            deltas = list(dq)
        labels = {"node": node}
        metrics.gauge("tpfl_convergence_delta_norm", delta, labels=labels)
        metrics.gauge("tpfl_convergence_rel_delta", rel, labels=labels)
        out = {"node": node, "round": rnd, "delta": delta, "rel": rel}
        event = None
        if len(deltas) == w and all(
            deltas[i] < deltas[i + 1] for i in range(w - 1)
        ):
            event = "divergence"
        elif len(deltas) == w and all(
            d / max(norm, _EPS) < self.PLATEAU_REL for d in deltas
        ):
            event = "plateau"
        if event:
            metrics.counter(
                f"tpfl_convergence_{event}_total", labels=labels
            )
            flight.record(
                node,
                {
                    "kind": "event",
                    "name": event,
                    "node": node,
                    "trace": "",
                    "t": time.monotonic(),
                    "round": rnd,
                    "delta_norm": _round(delta, 6),
                    "rel_delta": _round(rel, 8),
                },
            )
            out["event"] = event
        return out

    def observe_loss(
        self, node: str, ordinal: int, loss: float
    ) -> "float | None":
        """Record one fit's train loss; returns the current slope once
        the window is full (loss units per fit)."""
        if not Settings.LEDGER_ENABLED:
            return None
        w = self._window()
        with self._lock:
            dq = self._losses.setdefault(node, deque(maxlen=w))
            dq.append((int(ordinal), float(loss)))
            points = list(dq)
        if len(points) < 2:
            return None
        xs = [p[0] for p in points]
        ys = [p[1] for p in points]
        n = len(xs)
        mx = sum(xs) / n
        my = sum(ys) / n
        den = sum((x - mx) ** 2 for x in xs)
        slope = (
            sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / den
            if den > 0
            else 0.0
        )
        metrics.gauge(
            "tpfl_convergence_loss_slope", slope, labels={"node": node}
        )
        if len(points) == w and all(
            ys[i] < ys[i + 1] for i in range(n - 1)
        ):
            metrics.counter(
                "tpfl_convergence_divergence_total", labels={"node": node}
            )
            flight.record(
                node,
                {
                    "kind": "event",
                    "name": "divergence",
                    "node": node,
                    "trace": "",
                    "t": time.monotonic(),
                    "loss_slope": round(slope, 6),
                    "window": n,
                },
            )
        return slope

    def reset(self) -> None:
        with self._lock:
            self._prev.clear()
            self._deltas.clear()
            self._losses.clear()


# --- registry collector (pull-style occupancy gauges) ---------------------


def _ledger_collector(registry: Any) -> None:
    """Per-node ledger occupancy/flag gauges at scrape time — no
    instrumentation on the record path. Flushes first so a scrape
    observes scored entries, not pending ones."""
    contrib.flush()
    with contrib._lock:
        per_node = {
            n: (len(ring), sum(1 for e in ring if e["flagged"]))
            for n, ring in contrib._rings.items()
        }
    for node, (n_entries, n_flagged) in per_node.items():
        labels = {"node": node}
        registry.gauge("tpfl_ledger_entries", float(n_entries), labels=labels)
        registry.gauge("tpfl_ledger_flagged", float(n_flagged), labels=labels)


#: Process-wide singletons (one federation per process in every
#: simulation mode — same scope rationale as profiling.rounds).
contrib = ContributionLedger()
convergence = ConvergenceMonitor()
scorer = AnomalyScorer()

metrics.register_collector(_ledger_collector)
