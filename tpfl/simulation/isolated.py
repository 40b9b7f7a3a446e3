"""Opt-in process isolation for fallback fits.

The reference's Ray actor pool runs every learner in its own process,
so a crashing learner (or a native-library segfault) kills one actor,
which the pool flags and respawns (``actor_pool.py:203-357``). tpfl's
batched-vmap pool is threads in one process — faster (no object-store
round trips), but a hard crash would take all nodes down. With
``Settings.SIM_PROCESS_ISOLATION = True`` the pool's FALLBACK path
(jobs that can't batch) runs each fit in a spawned worker process
instead. Workers share one pool, so a crash breaks the WHOLE pool for
every in-flight job; ``isolated_fit`` rebuilds the pool and retries
each affected job once (serialized), so a dead worker ends up failing
only the job that crashed it while concurrent innocents complete on the
rebuilt pool — the reference's isolation property restored (modulo two
unrelated crashes hitting the same job's both attempts).

Scope: plain ``JaxLearner`` fits (no aggregator callbacks — SCAFFOLD /
FedProx state lives in-process; such jobs stay on the thread pool, with
a log line). The child rebuilds a real JaxLearner from shipped arrays,
so the fit math — including per-(seed, addr, round) shuffle seeding —
is identical to the in-process path (tested).
"""

from __future__ import annotations

import pickle
import threading
from typing import Any, Optional

import numpy as np

from tpfl.management.logger import logger
from tpfl.settings import Settings

_executor = None
_executor_lock = threading.Lock()
# Serializes bystander retries after a pool break: a crashing job's
# retry can then only break a pool while it alone holds the lock, so
# every other retrying job gets a fresh executor after it.
_retry_lock = threading.Lock()


def _child_init() -> None:
    """Worker initializer (runs before jax import in the child): pin
    isolated fits to the host CPU. The TPU belongs to the parent's
    batched-vmap path; a fleet of worker processes grabbing the chip
    would contend with it, and CPU f32 keeps isolated results exactly
    reproducible against a CPU parent (the parity test)."""
    import os

    # One process per chip: the parent may hold it. For this child's own
    # children the environment is enough...
    os.environ["JAX_PLATFORMS"] = "cpu"
    # ...but NOT for this child: the spawn bootstrap imports this module
    # (and so jax, which reads JAX_PLATFORMS at import) to find this
    # initializer, before the line above runs. A config update before
    # backend init is what takes effect here
    # (tests/test_simulation.py::test_isolated_fit_matches_inline).
    import jax

    jax.config.update("jax_platforms", "cpu")


def _get_executor():
    """Lazy spawn-context ProcessPoolExecutor; rebuilt after a crash."""
    global _executor
    with _executor_lock:
        if _executor is None:
            import multiprocessing as mp
            from concurrent.futures import ProcessPoolExecutor

            workers = int(Settings.SIM_WORKERS) or 4
            _executor = ProcessPoolExecutor(
                max_workers=workers,
                mp_context=mp.get_context("spawn"),
                initializer=_child_init,
            )
        return _executor


def _discard_executor(only: Any = None) -> None:
    """Tear down the current executor. With ``only``, discard it ONLY
    if it still IS the current one — a late-arriving failure handler
    for a pool that was already replaced must not shut down the fresh
    pool other jobs are retrying on (their pending futures would be
    cancelled, and CancelledError is not BrokenProcessPool)."""
    global _executor
    with _executor_lock:
        if only is not None and _executor is not only:
            ex = None
        else:
            ex, _executor = _executor, None
    if ex is not None:
        ex.shutdown(wait=False, cancel_futures=True)


def shutdown() -> None:
    """Tear down the worker pool (tests / reconfiguration)."""
    _discard_executor()


def _child_fit(payload: bytes) -> bytes:
    """Worker-process entry: rebuild a JaxLearner and run the REAL fit
    (same seeding, same compiled program shape as the inline path).

    Top-level function (spawn pickles it by reference). Returns encoded
    params via tpfl serialization — never pickle of arbitrary objects
    back into the parent. The fresh process has default Settings, so
    the result is encoded exact (no WIRE_DTYPE downcast)."""
    job = pickle.loads(payload)
    if job.get("_test_crash"):  # test hook: simulate a native crash
        import os

        os._exit(42)

    from tpfl.learning.dataset.export import Batches
    from tpfl.learning.dataset.tpfl_dataset import TpflDataset
    from tpfl.learning.jax_learner import JaxLearner
    from tpfl.learning.model import TpflModel

    module = pickle.loads(job["module"])
    model = TpflModel(module=module)
    model.set_parameters(job["params"])
    x, y = job["x"], job["y"]
    data = TpflDataset.from_arrays(x, y, x[:1], y[:1])
    learner = JaxLearner(
        model,
        data,
        addr=job["addr"],
        learning_rate=job["learning_rate"],
        batch_size=job["batch_size"],
    )
    # Inject the parent's exported batches verbatim (same export seed,
    # same round counter): the per-epoch shuffles reproduce exactly.
    learner._train_batches = Batches(
        x, y, job["batch_size"], seed=job["export_seed"]
    )
    learner._round_counter = job["round_counter"]
    learner.set_epochs(job["epochs"])
    fitted = learner.fit()
    # Dense on purpose: this is a same-host process round-trip, not the
    # gossip wire — a lossy WIRE_CODEC must not perturb the fit result.
    return fitted.encode_parameters(codec="dense")


def extract_job(learner: Any) -> Optional[bytes]:
    """Serialize a JaxLearner fit into a child-process payload, or None
    when the job is outside the isolation scope: aggregator callbacks
    (their state lives in-process), mutable collections, custom
    optimizer/loss, or an un-picklable module."""
    from tpfl.learning.jax_learner import (
        JaxLearner,
        _addr_seed,
        cross_entropy_loss,
        default_optimizer,
    )

    if not isinstance(learner, JaxLearner):
        return None
    if learner.callbacks:
        return None
    if learner._optimizer_factory is not default_optimizer:
        return None
    if learner._loss_fn is not cross_entropy_loss:
        return None
    model = learner.get_model()
    if model.aux_state:
        return None  # BatchNorm stats threading stays in-process
    try:
        module_bytes = pickle.dumps(model.module)
        # Dense: in-process hand-off to the child, not wire traffic.
        params = model.encode_parameters(codec="dense")
    except Exception:
        return None
    export_seed = (Settings.SEED or 0) + _addr_seed(learner.get_addr())
    batches = learner._train_data(export_seed)
    job = {
        "module": module_bytes,
        "params": params,
        "x": np.asarray(batches.x),
        "y": np.asarray(batches.y),
        "export_seed": batches.seed,
        "addr": learner.get_addr(),
        "learning_rate": learner.learning_rate,
        "batch_size": learner.batch_size,
        "epochs": learner.epochs,
        "round_counter": learner._round_counter,
    }
    return pickle.dumps(job)


def isolated_fit(learner: Any, payload: Optional[bytes] = None) -> Any:
    """Run one fit in a worker process; apply the result to the
    learner.

    Workers share one ProcessPoolExecutor, and CPython marks the WHOLE
    pool broken when any worker dies — so a crash surfaces
    BrokenProcessPool to every in-flight job, innocents included.
    Containment therefore takes two steps: rebuild the pool, then retry
    the job once (retries serialized, so a crashing job's retry breaks
    only a pool it holds exclusively). The job whose payload actually
    crashes the worker fails both attempts and raises; a concurrent
    innocent fails only if a second, unrelated crash also lands on its
    retry."""
    from concurrent.futures.process import BrokenProcessPool

    if payload is None:
        payload = extract_job(learner)
    if payload is None:
        raise ValueError("learner is outside the isolation scope")
    ex = _get_executor()
    try:
        result = ex.submit(_child_fit, payload).result()
    except BrokenProcessPool:
        _discard_executor(only=ex)  # replace the broken pool, not a successor
        with _retry_lock:
            ex2 = _get_executor()
            try:
                result = ex2.submit(_child_fit, payload).result()
            except BrokenProcessPool as e:
                _discard_executor(only=ex2)
                raise RuntimeError(
                    f"isolated fit worker died (both attempts): {e}"
                ) from e
    model = learner.get_model()
    # build_copy(params=bytes) restores the child's contributors and
    # num_samples from the payload itself.
    fitted = model.build_copy(params=result)
    learner.set_model(fitted)
    learner._round_counter += 1
    learner._last_fit_model = fitted
    logger.debug(learner.get_addr(), "isolated fit complete")
    return fitted
