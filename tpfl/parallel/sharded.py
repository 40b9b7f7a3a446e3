"""ShardedTrainer — dp/FSDP training of ONE large model over the mesh.

The reference has no intra-learner parallelism at all (SURVEY §2.10:
Lightning single-process, ``torch.set_num_threads(1)``). This is the
TPU-idiomatic seam: a jitted train step whose batch is sharded over a
``dp`` axis and (optionally) whose parameters/optimizer state are
sharded FSDP-style; XLA inserts the gradient all-reduce / all-gather
collectives over ICI. Plugs into a Learner via ``optimizer_factory`` /
custom fit, or is used directly (``__graft_entry__.py``'s dry run).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpfl.learning.jax_learner import cross_entropy_loss, default_optimizer


def fsdp_spec(leaf: Any, axis: str, axis_size: int) -> PartitionSpec:
    """Per-leaf FSDP heuristic: shard the last divisible dim; replicate
    small/indivisible leaves.

    Why the LAST dim: any dim gives the same 1/axis_size storage, but
    kernels are [..., in, out] and the backward w.r.t. activations
    contracts over ``out`` — with ``out`` sharded, XLA resolves the
    cotangent with an all-reduce and it comes out replicated, so a
    following transpose/reshape (e.g. the CNN flatten's transpose)
    re-shards cleanly to batch sharding. Sharding ``in`` instead leaves
    cotangents feature-sharded and triggered XLA's "[SPMD] Involuntary
    full rematerialization" on the flatten reshape (seen in round 2's
    MULTICHIP log)."""
    shape = np.shape(leaf)
    if not shape:
        return PartitionSpec()
    for i in reversed(range(len(shape))):
        if shape[i] % axis_size == 0 and shape[i] >= axis_size:
            spec = [None] * len(shape)
            spec[i] = axis
            return PartitionSpec(*spec)
    return PartitionSpec()


class ShardedTrainer:
    """Data-parallel (+ optional FSDP) single-model training.

    Args:
        module: flax module.
        mesh: Mesh with a ``dp`` axis (at least).
        fsdp: shard params/opt-state over the dp axis per-leaf.
        learning_rate / optimizer_factory / loss_fn: as JaxLearner.
    """

    def __init__(
        self,
        module: Any,
        mesh: Mesh,
        fsdp: bool = False,
        learning_rate: float = 0.1,
        optimizer_factory: Optional[Callable] = None,
        loss_fn: Optional[Callable] = None,
        seed: int = 0,
    ) -> None:
        self.module = module
        self.mesh = mesh
        self.fsdp = fsdp
        self.axis = "dp"
        self._opt = (optimizer_factory or default_optimizer)(learning_rate)
        self._loss_fn = loss_fn or cross_entropy_loss
        self.seed = seed
        self._step_fn: Optional[Callable] = None
        self._step_aux_fn: Optional[Callable] = None

    # --- setup ---

    def _param_sharding(self, params: Any) -> Any:
        axis_size = self.mesh.shape[self.axis]
        if self.fsdp:
            return jax.tree_util.tree_map(
                lambda p: NamedSharding(
                    self.mesh, fsdp_spec(p, self.axis, axis_size)
                ),
                params,
            )
        return jax.tree_util.tree_map(
            lambda p: NamedSharding(self.mesh, PartitionSpec()), params
        )

    def init(self, input_shape: tuple[int, ...]) -> tuple[Any, Any]:
        """(params, opt_state), placed on the mesh (aux-free modules;
        BatchNorm'd models use :meth:`init_with_aux`)."""
        params, aux, opt_state = self.init_with_aux(input_shape)
        if aux:
            raise ValueError(
                f"Module has mutable collections {sorted(aux)} — use "
                f"init_with_aux() and train_step_with_aux()."
            )
        return params, opt_state

    def init_with_aux(self, input_shape: tuple[int, ...]) -> tuple[Any, Any, Any]:
        """(params, aux, opt_state), placed on the mesh. ``aux`` holds
        mutable collections (``batch_stats`` for BatchNorm models like
        ResNet18), replicated across the mesh — stats are small and are
        updated by the same replicated computation on every shard."""
        dummy = jnp.zeros((1, *input_shape), jnp.float32)
        variables = self.module.init(
            jax.random.PRNGKey(self.seed), dummy, train=False
        )
        params = variables["params"]
        aux = {k: v for k, v in variables.items() if k != "params"}
        params = jax.device_put(params, self._param_sharding(params))
        if aux:
            rep = NamedSharding(self.mesh, PartitionSpec())
            aux = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, rep), aux
            )
        opt_state = self._opt.init(params)
        return params, aux, opt_state

    def shard_batch(self, x: Any, y: Any) -> tuple[Any, Any]:
        """Shard the batch dimension over dp."""
        sh = NamedSharding(self.mesh, PartitionSpec(self.axis))
        return jax.device_put(jnp.asarray(x), sh), jax.device_put(
            jnp.asarray(y), sh
        )

    # --- step ---

    def _gather_for_compute(self, p: Any) -> Any:
        """ZeRO-3 semantics for FSDP: gather the sharded weights for
        compute (all-gather, O(params)) and keep the activations
        batch-sharded. Without this, GSPMD computes WITH sharded
        weights — tensor-parallel style — and re-shards ACTIVATIONS
        between layers, moving O(batch) bytes per step (caught by
        tests/test_scaling_model.py). The constraint's transpose
        reduce-scatters the grads back to the param sharding. No-op
        when fsdp is off (params already replicated)."""
        if not self.fsdp:
            return p
        replicated = NamedSharding(self.mesh, PartitionSpec())
        return jax.lax.with_sharding_constraint(
            p, jax.tree_util.tree_map(lambda _: replicated, p)
        )

    def _build_step(self, params: Any) -> Callable:
        module = self.module
        loss_fn = self._loss_fn
        opt = self._opt
        param_sh = self._param_sharding(params)
        batch_sh = NamedSharding(self.mesh, PartitionSpec(self.axis))

        gather = self._gather_for_compute

        def step(params, opt_state, x, y):
            def loss_of(p):
                p = gather(p)
                logits = module.apply({"params": p}, x, train=False)
                return loss_fn(logits, y).mean()

            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        # in/out shardings pin the layout; XLA inserts the collectives
        # (grad all-reduce over dp; FSDP gather/scatter when params are
        # sharded).
        return jax.jit(
            step,
            donate_argnums=(0, 1),
            in_shardings=(
                param_sh,
                None,  # opt state: let XLA mirror the param layout
                batch_sh,
                batch_sh,
            ),
            out_shardings=None,
        )

    def train_step(
        self, params: Any, opt_state: Any, x: Any, y: Any
    ) -> tuple[Any, Any, Any]:
        if self._step_fn is None:
            self._step_fn = self._build_step(params)
        return self._step_fn(params, opt_state, x, y)

    # --- aux-threaded variant (BatchNorm models) ---

    def _build_step_aux(self, params: Any) -> Callable:
        module = self.module
        loss_fn = self._loss_fn
        opt = self._opt
        param_sh = self._param_sharding(params)
        batch_sh = NamedSharding(self.mesh, PartitionSpec(self.axis))

        gather = self._gather_for_compute

        def step(params, aux, opt_state, x, y):
            def loss_of(p):
                p = gather(p)  # ZeRO-3 gather — see _gather_for_compute
                logits, new_aux = module.apply(
                    {"params": p, **aux}, x, train=True, mutable=list(aux)
                )
                return loss_fn(logits, y).mean(), new_aux

            (loss, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, new_aux, opt_state, loss

        return jax.jit(
            step,
            donate_argnums=(0, 1, 2),
            # aux/opt-state shardings None: they arrive replicated from
            # init_with_aux and jit keeps the layout.
            in_shardings=(param_sh, None, None, batch_sh, batch_sh),
            out_shardings=None,
        )

    def train_step_with_aux(
        self, params: Any, aux: Any, opt_state: Any, x: Any, y: Any
    ) -> tuple[Any, Any, Any, Any]:
        """One dp/FSDP step threading mutable collections: returns
        (params, aux, opt_state, loss). BatchNorm runs with
        ``train=True`` on the *logical* (whole) batch: under jit the
        sharded batch is one logical array, so XLA computes the global
        batch mean/var with cross-shard collectives — sync-BN semantics
        for free, and the updated stats stay replicated."""
        if self._step_aux_fn is None:
            self._step_aux_fn = self._build_step_aux(params)
        return self._step_aux_fn(params, aux, opt_state, x, y)
