"""VmapFederation — a whole federation as one XLA program.

Replaces the reference's Ray actor pool simulation
(``simulation/actor_pool.py:69``: N learner processes, pickled weight
round-trips per round) with the TPU-native design from SURVEY §7: all N
homogeneous nodes' parameters are stacked on a leading ``nodes`` axis,
local training is ``vmap`` of a ``lax.scan`` epoch, and FedAvg is an
exact masked weighted reduction over the node axis. Dynamic train sets
(the vote) become a 0/1 mask instead of re-sharding (SURVEY "hard
parts").

Since PR 9 every round program is BUILT AND RUN by the federation
engine (:class:`tpfl.parallel.engine.FederationEngine`) — this class is
the stable high-level API over it. The engine adds what this class
alone never had: gossip-as-collective folds under ``shard_map`` on a
multi-chip mesh (per-device partial sums psum-reduced over the
``nodes`` axis), automatic node-axis padding for node counts that do
not divide the mesh (zero-weight clone rows, exact no-ops under the
masked fold), and device-side multi-round windows
(:meth:`run_rounds`) that pay the host dispatch RTT once per window.

One round of a 100-node CIFAR federation is ONE jitted call: no Python
loop over nodes, no host round-trips, no serialization.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh

from tpfl.learning.jax_learner import cross_entropy_loss
from tpfl.management import profiling
from tpfl.parallel.engine import FederationEngine


class VmapFederation:
    """N-node federated training, vectorized over a ``nodes`` axis.

    Args:
        module: flax module (same architecture on every node).
        n_nodes: federation size N. If a mesh is given and N does not
            divide it, the node axis is padded to
            ``engine.padded_nodes`` with zero-weight clone rows (the
            stacked arrays this class returns carry the padded leading
            dimension; ``engine.unpad`` strips it host-side).
        mesh: optional Mesh with a ``nodes`` axis; node-stacked arrays
            are sharded over it (None = single device; ``"auto"`` =
            resolve from the ``SHARD_NODES``/``SHARD_DEVICES`` knobs).
        learning_rate / optimizer_factory: local optimizer (default
            SGD+momentum, see JaxLearner).
        loss_fn: (logits, labels) -> per-sample losses.
        seed: init seed (all nodes share the initial model, like the
            reference's init-weights gossip).
        algorithm: "fedavg" (default), "fedprox" (adds the proximal
            pull ``mu/2·||w - w_round_start||²`` to every local loss —
            same math as the protocol path's FedProxCallback), or
            "scaffold" (control-variate-corrected local steps; carry
            the state from :meth:`init_scaffold_state` through
            ``round(..., scaffold_state=...)`` — same Option-II math
            as the protocol path's ScaffoldCallback/Scaffold
            aggregator, vectorized over the node axis).
        prox_mu: FedProx proximal coefficient (algorithm="fedprox").
    """

    def __init__(
        self,
        module: Any,
        n_nodes: int,
        mesh: "Mesh | str | None" = None,
        learning_rate: float = 0.1,
        optimizer_factory: Optional[Callable] = None,
        loss_fn: Callable = cross_entropy_loss,
        seed: int = 0,
        aux_mode: str = "mean",
        algorithm: str = "fedavg",
        prox_mu: float = 0.01,
    ) -> None:
        self.engine = FederationEngine(
            module,
            n_nodes,
            mesh=mesh,
            learning_rate=learning_rate,
            optimizer_factory=optimizer_factory,
            loss_fn=loss_fn,
            seed=seed,
            aux_mode=aux_mode,
            algorithm=algorithm,
            prox_mu=prox_mu,
        )
        self.module = module
        self.n_nodes = int(n_nodes)
        # ``mesh="auto"`` resolves from the SHARD_* knobs; expose the
        # RESOLVED mesh (a Mesh or None), never the sentinel.
        self.mesh = self.engine.mesh
        self.learning_rate = float(learning_rate)
        self.seed = seed
        self.aux_mode = aux_mode
        self.algorithm = algorithm
        self.prox_mu = float(prox_mu)
        self._round_fn: Optional[Callable] = None
        self._round_aux_fn: Optional[Callable] = None
        self._round_scaffold_fn: Optional[Callable] = None

    # --- params ---

    def init_state(self, input_shape: tuple[int, ...]) -> tuple[Any, Any]:
        """(stacked params, stacked aux) — aux is ``{}`` for modules
        without mutable collections, else e.g. ``{"batch_stats": ...}``
        stacked on the node axis (BatchNorm'd models: ResNet18)."""
        return self.engine.init_state(input_shape)

    def init_params(self, input_shape: tuple[int, ...]) -> Any:
        """Stacked [N, ...] params, identical across nodes (aux-free
        modules; BatchNorm'd models use :meth:`init_state`)."""
        return self.engine.init_params(input_shape)

    def shard_data(self, xs: np.ndarray, ys: np.ndarray) -> tuple[Any, Any]:
        """Place node-stacked batch arrays [N, n_batches, b, ...] on the
        mesh (node axis sharded, padded to the device multiple)."""
        return self.engine.shard_data(xs, ys)

    # --- raw round programs (unwrapped: a caller may drive them from
    # inside its own jitted loop, where the observatory's per-call
    # probe would execute at trace time and record junk; jitted with
    # the LEGACY signatures — positional-static epochs, legacy
    # donation — so ``.lower(...)`` keeps working for the static
    # scaling analysis: ``tests/test_scaling_model.py`` and
    # ``__graft_entry__.py`` are the callers left, ROADMAP D5) ---

    def _build_round(self) -> Callable:
        eng = self.engine

        def round_impl(params, xs, ys, weights, epochs=1):
            fn = eng.raw_program(
                "plain", int(epochs), 1, 1,
                model_axes=eng.model_axes, layout=eng.layout.name,
            )
            p, _c, _cg, _a, losses = fn(
                eng.pad_stacked(params), {}, {}, {},
                eng.pad_stacked(xs), eng.pad_stacked(ys),
                eng.pad_weights(weights), eng.valid,
            )
            return p, losses

        return jax.jit(round_impl, static_argnums=(4,), donate_argnums=(0,))

    def _build_round_aux(self) -> Callable:
        eng = self.engine

        def round_impl(params, aux, xs, ys, weights, epochs=1):
            fn = eng.raw_program(
                "aux", int(epochs), 1, 1,
                model_axes=eng.model_axes, layout=eng.layout.name,
            )
            p, _c, _cg, a, losses = fn(
                eng.pad_stacked(params), {}, {}, eng.pad_stacked(aux),
                eng.pad_stacked(xs), eng.pad_stacked(ys),
                eng.pad_weights(weights), eng.valid,
            )
            return p, a, losses

        return jax.jit(
            round_impl, static_argnums=(5,), donate_argnums=(0, 1)
        )

    def _build_round_scaffold(self) -> Callable:
        eng = self.engine

        def round_impl(params, c_locals, c_global, aux, xs, ys, weights,
                       epochs=1):
            fn = eng.raw_program(
                "scaffold", int(epochs), 1, 1,
                model_axes=eng.model_axes, layout=eng.layout.name,
            )
            p, c, cg, a, losses = fn(
                eng.pad_stacked(params), eng.pad_stacked(c_locals), c_global,
                eng.pad_stacked(aux), eng.pad_stacked(xs),
                eng.pad_stacked(ys), eng.pad_weights(weights), eng.valid,
            )
            return p, c, cg, a, losses

        return jax.jit(
            round_impl, static_argnums=(7,), donate_argnums=(0, 1, 2, 3)
        )

    # --- SCAFFOLD (Karimireddy et al. 2019, Option II) ---

    def init_scaffold_state(self, params: Any) -> tuple[Any, Any]:
        """(c_locals [N, ...], c_global [...]) — zero control variates
        (the protocol path's ScaffoldCallback.on_fit_start equivalent,
        callbacks.py:90-96)."""
        return self.engine.init_scaffold_state(params)

    def round(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any] = None,
        epochs: int = 1,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
    ) -> tuple[Any, ...]:
        """Run one federated round. ``weights`` [N]: FedAvg weight per
        node (0 = not in the round's train set); default = uniform full
        participation.

        Returns ``(new stacked params, per-node losses)``; with ``aux``
        not None (mutable collections from :meth:`init_state` — possibly
        ``{}`` for aux-free modules, the API stays uniform) returns
        ``(params, aux, losses)`` — stats trained with ``train=True``
        and aggregated per :attr:`aux_mode`.

        algorithm="scaffold": pass ``scaffold_state`` from
        :meth:`init_scaffold_state`; returns
        ``(params, aux, scaffold_state, losses)`` (``aux`` is ``{}``
        for aux-free modules)."""
        if weights is None:
            weights = jnp.ones((self.n_nodes,), jnp.float32)
        weights = jnp.asarray(weights, jnp.float32)
        if self.algorithm == "scaffold":
            if scaffold_state is None:
                raise ValueError(
                    "algorithm='scaffold' requires scaffold_state "
                    "(init_scaffold_state(params))"
                )
            if self._round_scaffold_fn is None:
                # Observatory wrap at the API seam (not inside the
                # builders): the raw _build_round* fns may run inside a
                # caller's own jitted loop, where a per-call probe
                # would execute at trace time and record junk.
                self._round_scaffold_fn = profiling.observatory.wrap(
                    self._build_round_scaffold(),
                    f"vmap_round_scaffold:{profiling.module_tag(self.module)}",
                )
            c_locals, c_global = scaffold_state
            params, c_locals, c_global, aux_out, losses = (
                self._round_scaffold_fn(
                    params, c_locals, c_global,
                    {} if aux is None else aux, xs, ys, weights, epochs,
                )
            )
            return params, aux_out, (c_locals, c_global), losses
        if aux is not None:
            if self._round_aux_fn is None:
                self._round_aux_fn = profiling.observatory.wrap(
                    self._build_round_aux(),
                    f"vmap_round_aux:{profiling.module_tag(self.module)}",
                )
            return self._round_aux_fn(params, aux, xs, ys, weights, epochs)
        if self._round_fn is None:
            self._round_fn = profiling.observatory.wrap(
                self._build_round(),
                f"vmap_round:{profiling.module_tag(self.module)}",
            )
        return self._round_fn(params, xs, ys, weights, epochs)

    def run_rounds(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any] = None,
        epochs: int = 1,
        n_rounds: int = 1,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
        donate: Optional[bool] = None,
        schedule: Optional[Any] = None,
    ) -> tuple[Any, ...]:
        """``n_rounds`` federated rounds in ONE device dispatch (the
        engine's ``lax.fori_loop`` window — host dispatch RTT paid once
        per window, ``Settings.SHARD_ROUNDS_PER_DISPATCH`` sizes it for
        the learner integrations). Return conventions match
        :meth:`round`; ``n_rounds=1`` is the identical program.
        ``donate`` defaults to ``Settings.ENGINE_DONATE`` (the state
        buffers alias the outputs in place); ``donate=False`` keeps
        input buffers alive (a caller that re-feeds the same arrays:
        tests do). ``schedule`` (a
        :class:`~tpfl.parallel.engine.FedBuffSchedule`) runs the
        window ASYNC — per-round arrival masks with staleness-weighted
        folds, the FedBuff semantics of the gRPC tier moved on-device
        (see ``FederationEngine.run_rounds``)."""
        return self.engine.run_rounds(
            params, xs, ys, weights=weights, epochs=epochs,
            n_rounds=n_rounds, aux=aux, scaffold_state=scaffold_state,
            donate=donate, schedule=schedule,
        )

    # --- evaluation ---

    def evaluate(
        self, params: Any, xs: Any, ys: Any, aux: Optional[Any] = None
    ) -> tuple[Any, Any]:
        """Per-node (loss, accuracy) over node-stacked eval data."""
        return self.engine.evaluate(params, xs, ys, aux=aux)
