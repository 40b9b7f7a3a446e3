"""WindowPipeline — the free-running engine driver (Sebulba split).

Podracer's Sebulba architecture (PAPERS.md) pins host work and device
compute to SEPARATE streams and double-buffers between them. The fused
engine already compiles K federation rounds into one device dispatch
(:class:`~tpfl.parallel.engine.FederationEngine`), but a sequential
driver still pays, BETWEEN windows, the host-side costs the device
never needed to wait for: the telemetry fan-out
(``engine_obs.replay_window``), profiler bookkeeping, next-window data
staging, and the dispatch RTT itself.

This driver exploits what JAX gives for free — async dispatch (a
program call returns output FUTURES while the device works) and buffer
donation (window N+1 consumes window N's output buffers in place) — to
run the engine free:

::

    device |  win N  ||  win N+1  ||  win N+2  | ...
    host   | dispatch N+1 ; finalize N (telemetry replay, profiler)
           | stage N+2's data on the prefetch thread ; dispatch N+2 ...

Steady state: the device's dispatch queue is never empty, so dispatch
RTT and host work vanish from wall clock. How idle the device really
is between windows is read from a device trace, not inferred here
(``device_idle_pct`` and the idle time by ``tpfl:`` span, PERF.md): each
loop iteration is a ``tpfl:pipeline_window`` span
(:func:`tpfl.management.tracing.engine_span`) around its children.

Determinism: the pipeline reorders HOST work only — the device sees
the identical program sequence over the identical buffers, so
same-seed runs stay byte-identical to chained
``FederationEngine.run_rounds`` calls (tests/test_engine_async.py
proves it at 1 and 8 devices, donation report still clean).

Double-buffer ownership: with donation on, window N's input state is
consumed by the device program; the ONLY live copy of the federation
state is window N's output futures, which this driver chains straight
into window N+1's dispatch. At most two windows are ever in flight, so
at most two state buffers exist — the explicit double buffer.

Concurrency: the prefetch thread (:class:`WindowPrefetcher`) is a
named, single-slot stager guarded by ``tpfl.concurrency.make_lock``
(deadlock-ordering tracked under ``LOCK_TRACING``); it is joined at
every take and on shutdown — no thread outlives :meth:`run`.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from tpfl import concurrency
from tpfl.management import tracing
from tpfl.parallel.engine import (
    EngineWindow,
    FederationEngine,
    FedBuffSchedule,
    start_host_copy,
)
from tpfl.settings import Settings

# data_for(window_index, start_round, n_rounds) -> (xs, ys) or None
# (None = reuse the current window's arrays).
DataSupplier = Callable[[int, int, int], "Optional[tuple[Any, Any]]"]

# Live pipelines by owner addr — the shutdown seam: Node.stop and
# FaultInjector.crash interrupt a node's in-flight run via
# :func:`interrupt_for` so donated buffers retire cleanly instead of
# racing the teardown.
# guarded-by: _ACTIVE_LOCK
_ACTIVE: "dict[str, WindowPipeline]" = {}
_ACTIVE_LOCK = concurrency.make_lock("window_pipeline._ACTIVE_LOCK")


def interrupt_for(addr: str) -> bool:
    """Interrupt the pipeline currently running for ``addr`` (no-op
    False when none is registered). The run finishes its in-flight
    window, finalizes or abandons the handle, and returns — callers
    (Node.stop, FaultInjector.crash) get a clean join point instead of
    leaked prefetch threads and unreferenced donated buffers."""
    with _ACTIVE_LOCK:
        pipe = _ACTIVE.get(addr)
    if pipe is None:
        return False
    pipe.interrupt()
    return True


class WindowPrefetcher:
    """Single-slot background stager for the next window's data.

    One named thread per window: :meth:`start` launches it to run the
    supplier (shuffle + ``device_put`` placement — pure host/transfer
    work), :meth:`take` joins it and hands the staged arrays over. The
    slot is guarded by a :func:`tpfl.concurrency.make_lock` lock, and
    a thread is ALWAYS joined before the next starts and on
    :meth:`close` — the pipeline leaks no threads past its run.
    """

    def __init__(
        self, fn: DataSupplier, name: str = "tpfl-window-prefetch"
    ) -> None:
        self._fn = fn
        self._name = name
        self._lock = concurrency.make_lock("WindowPrefetcher._lock")
        # ephemeral: live thread handle — always joined before the next
        # stage and on close(); nothing to resume.
        self._thread: Optional[threading.Thread] = None
        # guarded-by: _lock — (window_index, staged_data, error)
        # ephemeral: in-flight staged data — re-staged from the data
        # supplier on the next run; device buffers cannot checkpoint.
        self._slot: Optional[tuple] = None

    def start(self, widx: int, start_round: int, n_rounds: int) -> None:
        """Stage window ``widx``'s data in the background (joins any
        previous stage first — one in flight)."""
        self.close()

        def work() -> None:
            out, err = None, None
            try:
                out = self._fn(widx, start_round, n_rounds)
            except BaseException as e:  # surfaced at take()
                err = e
            with self._lock:
                self._slot = (widx, out, err)

        self._thread = threading.Thread(
            target=work, name=f"{self._name}[{widx}]", daemon=True
        )
        self._thread.start()

    def take(self, widx: int) -> "Optional[tuple[Any, Any]]":
        """Join the stage and return window ``widx``'s staged data
        (None when nothing was staged for it); re-raises a supplier
        error on the caller's thread."""
        self.close()
        with self._lock:
            slot, self._slot = self._slot, None
        if slot is None:
            return None
        staged_widx, out, err = slot
        if err is not None:
            raise err
        return out if staged_widx == widx else None

    def close(self) -> None:
        """Join any in-flight stage (idempotent)."""
        t, self._thread = self._thread, None
        if t is not None:
            t.join()


class WindowPipeline:
    """Free-running multi-window driver over one engine.

    :meth:`run` covers ``n_rounds`` federation rounds in windows of
    ``window`` rounds each, keeping one window in flight ahead of the
    host: window N+1 is DISPATCHED before window N is FINALIZED, so
    the telemetry fan-out, profiler rows and next-window data staging
    all overlap device compute. Results, side effects and bytes match
    a sequential chain of :meth:`FederationEngine.run_rounds` calls
    over the same per-window data.

    Attributes:
        windows_run: dispatched window count from the last :meth:`run`.
    """

    def __init__(self, engine: FederationEngine) -> None:
        self.engine = engine
        # unguarded: written only by the run() thread; cross-thread
        # readers (tests) read after run() returns.
        # ephemeral: per-run diagnostic — every run() resets it; the
        # durable cadence state rides the engine snapshot
        # (_materialize_snapshot -> engine.export_state).
        self.windows_run = 0
        # Cross-thread stop flag (interrupt_for / Node.stop) — honored
        # at exactly the between-dispatch granularity should_stop is.
        # ephemeral: live control signal — a resumed run starts
        # unaborted by construction.
        self._abort = threading.Event()

    def interrupt(self) -> None:
        """Request the current :meth:`run` stop at the next window
        boundary (thread-safe; sticky until the next run starts)."""
        self._abort.set()

    def _materialize_snapshot(
        self, snap: tuple, snapshot_to: Callable[[int, dict], None]
    ) -> None:
        """Consume a pending cadence snapshot: the D2H copies started
        at dispatch have had a full device window to land, so the
        ``np.asarray`` inside ``export_state`` reads host memory. The
        engine's ``_rounds_done`` already equals the snapshotted
        window's position here (it advances at dispatch, and the next
        dispatch hasn't happened yet) — ``rounds_at`` pins it anyway."""
        rounds_at, p, a, ss = snap
        state = self.engine.export_state(p, aux=a, scaffold_state=ss)
        state["rounds_done"] = int(rounds_at)
        snapshot_to(int(rounds_at), state)

    def run(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any] = None,
        epochs: int = 1,
        n_rounds: int = 1,
        window: Optional[int] = None,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
        donate: Optional[bool] = None,
        schedule: Optional[FedBuffSchedule] = None,
        data_for: Optional[DataSupplier] = None,
        prefetch: Optional[bool] = None,
        should_stop: Optional[Callable[[], bool]] = None,
        weights_for: Optional[Callable[[int], Any]] = None,
        snapshot_every: int = 0,
        snapshot_to: Optional[Callable[[int, dict], None]] = None,
        owner: Optional[str] = None,
    ) -> tuple[Optional[tuple], int]:
        """Run ``n_rounds`` rounds free-running; returns
        ``(result, rounds_done)`` where ``result`` follows
        ``run_rounds``' return conventions for the LAST window (None
        if ``should_stop`` fired before the first dispatch).

        ``window`` (rounds per dispatch) defaults to
        ``Settings.SHARD_ROUNDS_PER_DISPATCH``. ``schedule`` spans the
        FULL run and is carved into per-window slices
        (:meth:`FedBuffSchedule.window`); per-round ``weights``
        ``[n_rounds, n]`` are sliced the same way. ``data_for``
        supplies each window's (possibly reshuffled, mesh-placed) data
        — staged on the :class:`WindowPrefetcher` thread when
        ``prefetch`` (default ``Settings.ENGINE_PREFETCH``) is on, or
        inline otherwise; both stagings are the same pure function of
        the window index, so the knob never changes bytes.
        ``should_stop`` is polled between dispatches (interrupt
        honoring at exactly the sequential driver's granularity).

        ISSUE-17 elastic hooks: ``weights_for(widx)`` supplies each
        window's fold-weight vector — the membership re-mask seam
        (churn between windows edits weights only; the compiled
        program and its shapes never move), overriding ``weights``
        when given. ``snapshot_every``/``snapshot_to`` arm cadence
        checkpointing: every K-th window's output state is snapshotted
        OFF the critical path — the D2H copy starts non-blocking at
        dispatch (:func:`~tpfl.parallel.engine.start_host_copy`) and
        materializes at the NEXT loop top, before the dispatch that
        would donate those buffers away, so the device pipeline never
        stalls on checkpoint I/O. ``snapshot_to(rounds_done, state)``
        receives :meth:`FederationEngine.export_state` output.
        ``owner`` registers this run for :func:`interrupt_for`."""
        eng = self.engine
        window = max(
            1,
            int(
                window
                if window is not None
                else Settings.SHARD_ROUNDS_PER_DISPATCH
            ),
        )
        if prefetch is None:
            prefetch = bool(Settings.ENGINE_PREFETCH)
        if schedule is not None and schedule.n_rounds != int(n_rounds):
            raise ValueError(
                f"schedule covers {schedule.n_rounds} rounds for a "
                f"{n_rounds}-round run"
            )
        w = None if weights is None else weights
        per_round_w = getattr(w, "ndim", 1) == 2
        scaffold = scaffold_state is not None
        has_aux = aux is not None

        prefetcher = (
            WindowPrefetcher(data_for)
            if (prefetch and data_for is not None)
            else None
        )
        self.windows_run = 0
        self._abort.clear()
        if owner is not None:
            with _ACTIVE_LOCK:
                _ACTIVE[owner] = self
        snap_every = max(0, int(snapshot_every)) if snapshot_to else 0
        # (rounds_done_after_window, params, aux, scaffold_state) of a
        # window whose host copy is in flight; materialized at the next
        # loop top, BEFORE the dispatch that donates those buffers.
        snap_pending: Optional[tuple] = None
        pending: Optional[EngineWindow] = None
        result: Optional[tuple] = None
        done = 0
        widx = 0
        cur_xs, cur_ys = xs, ys
        try:
            while done < int(n_rounds):
                # The window about to be dispatched, by its first round
                # (the engine's own count: what its spans are tagged with).
                first = eng._rounds_done
                with tracing.engine_span("pipeline_window", first):
                    if snap_pending is not None:
                        with tracing.engine_span("snapshot", first):
                            self._materialize_snapshot(snap_pending, snapshot_to)
                        snap_pending = None
                    if self._abort.is_set() or (
                        should_stop is not None and should_stop()
                    ):
                        break
                    k = min(window, int(n_rounds) - done)
                    if weights_for is not None:
                        # The elastic re-mask seam: membership churn since
                        # the last window lands here as a weight-vector
                        # edit — same program, same shapes, zero recompile.
                        w = weights_for(widx)
                        per_round_w = getattr(w, "ndim", 1) == 2
                    # This window's data: taken from the prefetch thread
                    # (staged while the previous window ran) or computed
                    # inline — same supplier, same bytes.
                    if data_for is not None:
                        with tracing.engine_span("data_take", first):
                            staged = (
                                prefetcher.take(widx)
                                if (prefetcher is not None and widx > 0)
                                else data_for(widx, done, k)
                            )
                        if staged is not None:
                            cur_xs, cur_ys = staged
                    handle = eng.dispatch_window(
                        params,
                        cur_xs,
                        cur_ys,
                        weights=(w[done:done + k] if per_round_w else w),
                        epochs=epochs,
                        n_rounds=k,
                        aux=aux,
                        scaffold_state=scaffold_state,
                        donate=donate,
                        schedule=(
                            None if schedule is None else schedule.window(done, k)
                        ),
                    )
                    # Stage the NEXT window's data while the device works
                    # and before this host thread dives into finalize.
                    nxt = done + k
                    if prefetcher is not None and nxt < int(n_rounds):
                        prefetcher.start(
                            widx + 1, nxt, min(window, int(n_rounds) - nxt)
                        )
                    if pending is not None:
                        # Window N's host leg (telemetry replay, profiler
                        # rows) overlaps window N+1's device leg.
                        result = pending.finalize()
                    # Chain the output futures straight into the next
                    # dispatch — the double buffer: with donation on these
                    # are the only live copy of the federation state.
                    params = handle.params
                    if scaffold:
                        aux = handle.aux
                        scaffold_state = handle.scaffold_state
                    elif has_aux:
                        aux = handle.aux
                    pending = handle
                    done += k
                    widx += 1
                    self.windows_run += 1
                    if snap_every and widx % snap_every == 0:
                        # Cadence checkpoint: start the non-blocking D2H
                        # copy NOW (it completes while the device runs this
                        # window); np.asarray at the next loop top reads
                        # host memory — the copy_to_host_async host leg.
                        start_host_copy(params)
                        if aux is not None:
                            start_host_copy(aux)
                        if scaffold:
                            start_host_copy(scaffold_state)
                        snap_pending = (
                            done,
                            params,
                            aux,
                            scaffold_state if scaffold else None,
                        )
        finally:
            if owner is not None:
                with _ACTIVE_LOCK:
                    if _ACTIVE.get(owner) is self:
                        del _ACTIVE[owner]
            if prefetcher is not None:
                prefetcher.close()
            if pending is not None:
                if self._abort.is_set():
                    # Interrupted shutdown (Node.stop / fault injector):
                    # retire the donated buffers without the telemetry
                    # fan-out — the handle must not outlive the run.
                    pending.abandon()
                    result = None
                else:
                    with tracing.engine_span(
                        "pipeline_window", pending._window_start
                    ):
                        result = pending.finalize()
        if snap_pending is not None and not self._abort.is_set():
            # The run ended with a copy still in flight (final window
            # hit the cadence): no further dispatch will donate these
            # buffers, so materializing here is safe and loses nothing.
            with tracing.engine_span("snapshot", eng._rounds_done):
                self._materialize_snapshot(snap_pending, snapshot_to)
        return result, done
