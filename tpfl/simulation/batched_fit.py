"""Batched local training — many learners' fits as one XLA program.

This is the bridge between the protocol world (N independent ``Node``
objects, each with a :class:`JaxLearner`) and the vectorized TPU
execution layer (``tpfl.parallel.VmapFederation``): a group of
homogeneous fit jobs is stacked on a leading ``nodes`` axis and trained
by ONE jitted ``vmap(local_fit)`` call. Replaces the reference's
per-learner Ray actor dispatch (``actor_pool.py:39-66``) where each fit
is a separate process round-trip.

Semantics vs ``JaxLearner.fit``: identical optimizer/loss/correction
handling and callback lifecycle; the one divergence is that the batch
order is shuffled once per round (not per epoch) because all epochs run
inside the compiled program. Nodes with fewer batches than the group
max are padded with masked no-op batches, so partitions of unequal size
batch together exactly.

The compiled program itself is built by the federation engine
(``tpfl.parallel.engine.build_batched_fit_program`` — the one seam the
vmapped federation and this pool ride), and when
``Settings.SHARD_NODES`` is on with a multi-chip host the stacked node
axis is placed over the ``nodes`` mesh
(``engine.maybe_nodes_mesh``), so pool fits run SPMD across chips.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpfl.learning.jax_learner import JaxLearner
from tpfl.management import ledger, profiling
from tpfl.management.logger import logger
from tpfl.parallel.engine import build_batched_fit_program, maybe_nodes_mesh
from tpfl.parallel.mesh import federation_sharding
from tpfl.settings import Settings


def job_signature(learner: JaxLearner) -> tuple:
    """Hashable homogeneity key: jobs with equal signatures can share
    one compiled batched program."""
    model = learner.get_model()
    params = model.get_parameters()
    leaves = jax.tree_util.tree_leaves(params)
    # dtype via np.dtype(p.dtype), NOT np.asarray(p): asarray of a jax
    # leaf copies the whole tensor to host just to read its dtype —
    # once per leaf per learner per round (caught by the sync lint).
    shapes = tuple(
        (tuple(np.shape(p)), np.dtype(p.dtype).name) for p in leaves
    )
    treedef = str(jax.tree_util.tree_structure(params))
    aux_def = str(jax.tree_util.tree_structure(model.aux_state or {}))
    return (
        repr(model.module),
        treedef,
        shapes,
        aux_def,
        learner.batch_size,
        learner.epochs,
        learner.learning_rate,
        learner._optimizer_factory,
        learner._loss_fn,
        tuple(sorted(cb.get_name() for cb in learner.callbacks)),
    )


class BatchedFitProgram:
    """Compiled ``vmap(local_fit)`` for one job signature.

    The compiled function is cached per (signature, n_batches, epochs);
    re-stacking data each round re-uses it as long as shapes repeat.
    """

    def __init__(self, learner: JaxLearner) -> None:
        module = learner._module()
        self._module = module
        self._opt = learner._tx
        self._loss_fn = learner._loss_fn
        self._has_aux = bool(learner.get_model().aux_state)
        # Gradient-tracking programs (SCAFFOLD: a callback set
        # wants_avg_grad) additionally accumulate the raw per-step
        # gradients; job_signature includes the callback names, so
        # tracking and plain jobs never share a program.
        self._track = any(
            getattr(cb, "wants_avg_grad", False) for cb in learner.callbacks
        )
        self._fns: dict[tuple[int, int], Callable] = {}

    def _build(self, epochs: int) -> Callable:
        # The program is the engine's masked vmapped local fit — ONE
        # builder shared with the pod-scale federation seam, so the
        # pool and the sharded engine can never drift numerically.
        return build_batched_fit_program(
            self._module,
            self._opt,
            self._loss_fn,
            self._has_aux,
            self._track,
            epochs,
        )

    def run(
        self,
        stacked_params: Any,
        stacked_aux: Any,
        stacked_corr: Any,
        stacked_anchor: Any,
        mus: np.ndarray,
        xs: np.ndarray,
        ys: np.ndarray,
        bmask: np.ndarray,
        epochs: int,
    ) -> tuple[Any, Any, Any, Any]:
        key = (int(xs.shape[1]), int(epochs))
        fn = self._fns.get(key)
        # Per-program shape cache: every distinct (n_batches, epochs)
        # is a fresh XLA compile — the observatory's counters are how a
        # shape-churning round schedule shows up before it hurts.
        profiling.observatory.cache_event("batched_shape_fns", hit=fn is not None)
        if fn is None:
            fn = self._fns[key] = profiling.observatory.wrap(
                self._build(epochs),
                f"batched_fit:{profiling.module_tag(self._module)}",
            )
        return fn(
            stacked_params,
            stacked_aux,
            stacked_corr,
            stacked_anchor,
            jnp.asarray(mus),
            jnp.asarray(xs),
            jnp.asarray(ys),
            jnp.asarray(bmask),
        )


_programs: dict[tuple, BatchedFitProgram] = {}


def _stack(trees: list[Any]) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _unstack(tree: Any, n: int) -> list[Any]:
    return [jax.tree_util.tree_map(lambda x: x[i], tree) for i in range(n)]


def run_batched_fits(
    signature: tuple, learners: list[JaxLearner]
) -> list[JaxLearner]:
    """Train every learner in ``learners`` (all sharing ``signature``)
    through one vmapped program, chunked to ``SIM_MAX_BATCH_NODES``.

    Mutates each learner's model in place via the same host-side
    lifecycle as ``JaxLearner.fit`` (prepare_fit/finish_fit). Returns
    the learners of FAILED chunks only — already-trained chunks are
    final, so the caller must not re-fit them."""
    prog = _programs.get(signature)
    profiling.observatory.cache_event("batched_programs", hit=prog is not None)
    if prog is None:
        prog = _programs[signature] = BatchedFitProgram(learners[0])

    chunk = max(int(Settings.SIM_MAX_BATCH_NODES), 1)
    failed: list[JaxLearner] = []
    for i in range(0, len(learners), chunk):
        part = learners[i : i + chunk]
        try:
            _run_chunk(prog, part)
        except Exception as e:
            logger.info(
                "simulation",
                f"Batched chunk of {len(part)} nodes failed ({e}); "
                "those nodes fall back to inline fits",
            )
            failed.extend(part)
    return failed


def _run_chunk(prog: BatchedFitProgram, learners: list[JaxLearner]) -> None:
    # Interrupts delivered before dispatch get JaxLearner's skip
    # treatment (unchanged model, zero FL weight). Once the compiled
    # round launches it is not interruptible — that is the cost of the
    # one-program batch (the inline path can still stop between epochs).
    active = []
    for ln in learners:
        if ln._interrupt.is_set():
            ln._interrupt.clear()
            logger.info(ln.get_addr(), "Fit skipped: interrupted before batch")
            ln.skip_fit()
        else:
            active.append(ln)
    learners = active
    if not learners:
        return

    epochs = learners[0].epochs
    jobs = []
    for ln in learners:
        model, initial, correction, mu, batches = ln.prepare_fit()
        xs, ys = batches.stacked(epoch=ln._round_counter * 10_000)
        ln._round_counter += 1
        jobs.append(
            {
                "learner": ln,
                "model": model,
                "initial": initial,
                "correction": correction,
                "mu": mu,
                "xs": xs,
                "ys": ys,
                "num_samples": batches.num_samples,
            }
        )

    # Pad every node's data to the chunk's max batch count; the mask
    # turns padding batches into exact no-ops inside the program.
    max_b = max(j["xs"].shape[0] for j in jobs)
    xs_l, ys_l, mask_l = [], [], []
    for j in jobs:
        nb = j["xs"].shape[0]
        pad = max_b - nb
        x, y = j["xs"], j["ys"]
        if pad:
            x = np.concatenate([x, np.zeros((pad, *x.shape[1:]), x.dtype)])
            y = np.concatenate([y, np.zeros((pad, *y.shape[1:]), y.dtype)])
        xs_l.append(x)
        ys_l.append(y)
        mask_l.append(
            np.concatenate([np.ones(nb, np.float32), np.zeros(pad, np.float32)])
        )

    # Bucket the node axis to the next power of two: group sizes drift
    # round to round (a straggler missing the batching window shrinks
    # the group by one), and every distinct vmap width is a fresh XLA
    # compile. Dummy slots replicate node 0 with an all-zero batch mask
    # (pure no-ops) and their outputs are discarded.
    bucket = 1
    while bucket < len(jobs):
        bucket *= 2
    for _ in range(bucket - len(jobs)):
        xs_l.append(xs_l[0])
        ys_l.append(ys_l[0])
        mask_l.append(np.zeros_like(mask_l[0]))

    param_trees = [
        jax.tree_util.tree_map(jnp.copy, j["model"].get_parameters())
        for j in jobs
    ]
    aux_trees = [
        jax.tree_util.tree_map(jnp.copy, j["model"].aux_state or {})
        for j in jobs
    ]
    corr_trees = [j["correction"] for j in jobs]
    # Anchors (round-start params for the proximal pull) must be
    # separate buffers from stacked_params — those are donated.
    anchor_trees = [j["initial"] for j in jobs]
    mus = [float(j["mu"]) for j in jobs]
    for _ in range(bucket - len(jobs)):
        param_trees.append(param_trees[0])
        aux_trees.append(aux_trees[0])
        corr_trees.append(corr_trees[0])
        anchor_trees.append(anchor_trees[0])
        mus.append(0.0)
    stacked_params = _stack(param_trees)
    stacked_aux = _stack(aux_trees)
    stacked_corr = _stack(corr_trees)
    stacked_anchor = _stack(anchor_trees)
    xs_s: Any = np.stack(xs_l)
    ys_s: Any = np.stack(ys_l)
    mask_s: Any = np.stack(mask_l)
    mus_s: Any = np.asarray(mus, np.float32)  # host-sync: mus is a host list

    # Pod-scale path (Settings.SHARD_NODES): place the stacked node
    # axis over the local `nodes` mesh — the pow-2 bucket above divides
    # a 2^k-chip host, so every chip trains an equal shard of the
    # chunk's nodes SPMD inside the one compiled program.
    mesh = maybe_nodes_mesh(bucket)
    if mesh is not None:
        sharding = federation_sharding(mesh)
        stacked_params, stacked_aux, stacked_corr, stacked_anchor = (
            jax.device_put(t, sharding)
            for t in (stacked_params, stacked_aux, stacked_corr, stacked_anchor)
        )
        xs_s, ys_s, mask_s = (
            jax.device_put(jnp.asarray(a), sharding)
            for a in (xs_s, ys_s, mask_s)
        )
        mus_s = jax.device_put(jnp.asarray(mus_s), sharding)

    # Round attribution: the chunk's dispatch gap and device compute
    # are charged to EVERY participating node — each node's round
    # blocked on this one program for its full duration.
    prof = profiling.rounds.enabled()
    t0 = time.monotonic() if prof else 0.0
    new_params, new_aux, losses, gsums = prog.run(
        stacked_params,
        stacked_aux,
        stacked_corr,
        stacked_anchor,
        mus_s,
        xs_s,
        ys_s,
        mask_s,
        epochs,
    )
    if prof:
        t1 = time.monotonic()
        jax.block_until_ready(losses)
        t2 = time.monotonic()
        for j in jobs:
            addr = j["learner"].get_addr()
            profiling.rounds.add(addr, "dispatch", t1 - t0)
            profiling.rounds.add(addr, "train", t2 - t1)
    # host-sync: ONE deliberate sync per chunk — the window is over and
    # every learner's finish_fit/metrics below consume losses on host.
    losses = np.asarray(losses)

    params_per_node = _unstack(new_params, len(jobs))
    aux_per_node = _unstack(new_aux, len(jobs))
    gsum_per_node = _unstack(gsums, len(jobs)) if prog._track else None
    for i, j in enumerate(jobs):
        ln, model = j["learner"], j["model"]
        n_steps = j["xs"].shape[0] * epochs
        avg_grad = None
        if gsum_per_node is not None:
            # The masked gsum summed only REAL batches; divide by the
            # node's own step count, not the padded chunk max.
            inv = jnp.float32(1.0 / max(n_steps, 1))
            avg_grad = jax.tree_util.tree_map(
                lambda g: g * inv, gsum_per_node[i]
            )
        ln.finish_fit(
            model,
            j["initial"],
            params_per_node[i],
            aux_per_node[i] if model.aux_state else None,
            n_steps,
            j["num_samples"],
            avg_grad=avg_grad,
        )
        if ln._in_experiment():
            logger.log_metric(
                ln.get_addr(), "train_loss", float(losses[i]), step=epochs - 1
            )
        # Same fit-seam loss tap as JaxLearner.fit (losses is already a
        # host array — no added device sync).
        if Settings.LEDGER_ENABLED:
            ledger.convergence.observe_loss(
                ln.get_addr(),
                (ln._round_counter - 1) * 10_000 + epochs - 1,
                float(losses[i]),
            )
        logger.debug(
            ln.get_addr(),
            f"batched fit ({len(jobs)} nodes): loss={float(losses[i]):.4f}",
        )
