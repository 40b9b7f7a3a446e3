"""Device-plane performance observatory.

PR-5's flight recorder stitched together the HOST and NETWORK plane; this
module is the DEVICE-plane counterpart, feeding the PR-5
:class:`~tpfl.management.telemetry.MetricsRegistry` /
:class:`~tpfl.management.telemetry.FlightRecorder`. Rates, MFU and idle
shares of a timed run come from the chip benchmark (``BENCHMARK.json``,
``benchmark/``), not from here:

- :class:`CompileObservatory` — wraps the jit/lower/compile seams
  (``jax_learner._shared_program``, ``VmapFederation._build_round*``,
  ``batched_fit.BatchedFitProgram``): program-cache hit/miss counters,
  the SET-UP ACCOUNT (every ``jax.monitoring`` compile event split into
  trace / lower / load / compile by program on ``time.monotonic``,
  nested events kept apart — :meth:`CompileObservatory.setup_account`),
  and RECOMPILATION detection keyed by
  (fn, abstract shapes/dtypes of the arguments) with a recompile-storm
  warning event when one program keeps re-specializing (the silent
  killer of steady-state throughput — every distinct vmap width or
  batch shape is a fresh XLA compile).
- :class:`RoundProfiler` — attributes each federation round's
  wall-clock into ``train`` / ``dispatch`` / ``fold`` / ``gossip`` /
  ``host_other`` components (the instrumented sites live in the
  learner, the batched-fit chunk, the aggregator, and the round
  stages). Beside it sit the two wall timers ``chip_smoke.py``'s
  ``sync`` phase reads (:func:`measure_dispatch_rtt`, a scalar-synced
  empty call) and :func:`best_of_wall_donated`.
- :class:`CostModel` — ONE FLOPs-accounting path shared with
  ``parallel/scaling.py``: XLA ``cost_analysis`` flops (with the
  scan-counted-once caveat in exactly one place), analytic model flops
  for the zoo architectures (2·M·K·N per layer, x3 fwd+bwd), peak
  FLOP/s lookup per device kind, and live per-round MFU gauges.
- :class:`HbmTracker` — per-device HBM high-water-mark gauges lifted
  from ``node_monitor``'s ``memory_stats`` read into a peak-tracking
  registry collector.
Gating: the metrics REGISTRY side (cache hit/miss counters, cache-size
gauges, HBM gauges) and the set-up account (fed only at compile seams:
no compile, no event) always record — cheap dict updates, PR-5's rule.
Everything that costs per-call work on a hot path (abstract-signature
extraction in :meth:`CompileObservatory.wrap`, round spans, the
``block_until_ready`` splits in the learner) is gated by
``Settings.PROFILING_ENABLED`` and collapses to one attribute read
when off — disabled profiling adds ZERO device dispatches
(``tests/test_profiling.py``: the disabled observatory and profiler
record nothing).

Concurrency: each tracker's shared state sits under its own
``make_lock`` leaf lock, never held while calling out of this module
(same discipline as telemetry.py). jax is imported lazily so importing
the management layer stays backend-free.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Iterator, Optional

from tpfl.concurrency import make_lock
from tpfl.management.telemetry import flight, metrics
from tpfl.settings import Settings

#: Peak dense bf16 FLOP/s per chip, keyed by the exact ``device_kind``
#: JAX reports — the library's single copy (:func:`peak_flops` reads it).
#: V5E MEASURED PATH ONLY: a row exists for a chip only once this repo
#: has run on it (``chip_smoke.py``). Source: Google Cloud TPU v5e
#: documentation, 197 TFLOP/s bf16. A kind missing here is ``None`` for
#: CPU-side callers and an ERROR on the chip path
#: (``tpfl.parallel.mesh.require_chip``) — never a default.
PEAK_FLOPS: dict[str, float] = {
    "TPU v5 lite": 197e12,  # v5e, as jax.devices()[0].device_kind spells it
}

#: Round components run 10 ms (device round) to minutes (timeout-bound
#: protocol rounds).
ROUND_BUCKETS: tuple[float, ...] = (
    0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)

#: The flight-recorder ring profiling events land in (a pseudo-node:
#: compile storms are process-scoped, not owned by any one federation
#: node).
PROFILING_RING = "_profiling"

#: Round attribution component names. ``host_other`` is the residual:
#: wall minus everything measured — attribution that cannot silently
#: drop time.
COMPONENTS = ("train", "dispatch", "fold", "gossip", "host_other")


def peak_flops(device: Any) -> "float | None":
    """Peak dense FLOP/s for a jax device, or None when unknown."""
    return PEAK_FLOPS.get(getattr(device, "device_kind", "") or "")


# --- compile observatory --------------------------------------------------


def _abstract_signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable abstraction of a call's arguments — what jit's cache
    key sees, approximately: (shape, dtype) per array leaf, VALUES for
    ints/bools/strs (static argnums recompile on value change), type
    only for floats (usually data, not structure)."""
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            out.append(("a", tuple(shape), str(dtype)))
        elif isinstance(leaf, (int, bool, str)):
            out.append(("s", leaf))
        elif leaf is None:
            out.append(("n",))
        else:
            out.append(("t", type(leaf).__name__))
    return tuple(out)


def module_tag(module: Any) -> str:
    """Short stable tag for an architecture — disambiguates per-fn
    signature sets (and metric labels) when several module configs
    share one program name, without unbounded label cardinality."""
    return f"{zlib.crc32(repr(module).encode()) & 0xFFFF:04x}"


#: The set-up account's phases, in the order a program passes them.
#: ``load`` and ``compile`` are the two outcomes of one JAX event.
SETUP_PHASES = ("trace", "lower", "load", "compile")

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
#: jax.monitoring duration event -> phase (the backend event is split
#: by the cache outcome seen before it on the same thread).
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _BACKEND_EVENT: "compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS_OF_EVENT = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_seconds",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_seconds",
}
#: Events a thread keeps open to the question "does a later event
#: contain this one?". Past it the older half is taken as outermost for
#: good (counted as ``frozen_events``: a parent that closes later would
#: find them counted beside it): memory is bounded whatever a trace
#: fires. The cells' set-ups keep under 11,000 nested events in all.
_PENDING_CAP = 65536
#: Rows of the account's ``nested`` table and ``first_calls`` list.
_NESTED_TOP = 16
_FIRST_CALLS_KEPT = 256


def _program_name(fun_name: str) -> str:
    """JAX names a program ``f`` while tracing and ``jit(f)`` from
    lowering on: one program, one row."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def process_started() -> "float | None":
    """When this process started, on ``time.monotonic``'s axis, or None
    where the platform cannot say. Linux: ``/proc/self/stat`` field 22
    is the start in clock ticks since boot (10 ms steps), read against
    ``CLOCK_BOOTTIME``. What lies between it and the first engine is the
    interpreter, the imports and the caller's own first acts."""
    import os

    try:
        with open("/proc/self/stat") as f:
            # The command (field 2) may hold spaces: count from its ")".
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK"
        )
    except (OSError, AttributeError, ValueError, IndexError):
        return None
    return time.monotonic() - age


class CompileObservatory:
    """Compile-seam accounting: cache hits/misses, the set-up account,
    recompile detection keyed by (fn, abstract shapes/dtypes).

    Two halves:

    - ALWAYS-ON (plain dict updates, PR-5 rule): the process
      program-cache traffic (:meth:`cache_event`,
      :meth:`cache_cleared`) — how the r3 "caches accrete forever" bug
      class becomes visible instead of latent — and the SET-UP ACCOUNT
      (:meth:`open_setup_account`, :meth:`setup_account`): what JAX's
      own monitoring says each program cost to trace, lower, load from
      the persistent cache or compile, on ``time.monotonic``. It is fed
      only where JAX compiles, so a steady state adds nothing to it.
    - GATED per-call work (``Settings.PROFILING_ENABLED``):
      :meth:`wrap` puts a signature probe in front of a jitted
      callable; a never-seen (fn, signature) is a (re)compilation, and
      when one fn accretes ``Settings.PROFILING_RECOMPILE_WARN``
      distinct signatures a ``recompile_storm`` event lands in the
      flight ring and the log.
    """

    def __init__(self) -> None:
        self._lock = make_lock("CompileObservatory._lock")
        # guarded-by: _lock
        self._signatures: dict[str, set] = {}
        # guarded-by: _lock
        self._warned: set[str] = set()
        # guarded-by: _lock. When the account opened on time.monotonic
        # (None: no listener yet). jax's listeners are global and
        # permanent, so it opens once and is never reset.
        self._account_started: "float | None" = None
        # guarded-by: _lock. (phase, fun_name) -> [outermost events,
        # their seconds, nested events, their seconds].
        self._by_name: dict[tuple[str, str], list] = {}
        # guarded-by: _lock
        self._cache = {
            "hits": 0, "misses": 0,
            "retrieval_seconds": 0.0, "saved_seconds": 0.0,
        }
        # guarded-by: _lock
        self._first_calls: deque = deque(maxlen=_FIRST_CALLS_KEPT)
        # guarded-by: _lock
        self._frozen_events = 0
        # unguarded: written once, in open_setup_account.
        self._process_started: "float | None" = None
        # unguarded: the registry's collectors run one at a time.
        # Series -> the value :meth:`publish` last brought it to.
        self._published: dict[tuple, float] = {}
        # Per thread: ``pending`` (events no later event contains so
        # far, oldest first, as (midpoint, seconds, _by_name key)) and
        # ``hit`` (the persistent cache answered since this
        # thread's last backend event).
        self._thread = threading.local()

    # --- always-on cache accounting ---

    def cache_event(self, cache: str, hit: bool) -> None:
        """One lookup against a process-lifetime compiled-program cache
        (``jax_learner._SHARED_PROGRAMS``, ``batched_fit._programs``,
        per-program shape caches...)."""
        metrics.counter(
            "tpfl_compiled_cache_requests_total",
            labels={"cache": cache, "result": "hit" if hit else "miss"},
        )

    def cache_cleared(self, dropped: int) -> None:
        """``clear_compiled_caches`` ran; ``dropped`` programs freed."""
        metrics.counter("tpfl_compiled_cache_clears_total")
        metrics.counter("tpfl_compiled_cache_dropped_total", float(dropped))

    # --- gated recompile detection ---

    def wrap(self, fn: Callable, name: str) -> Callable:
        """Signature-probe wrapper around a jitted callable. With
        profiling off the wrapper is one attribute read + passthrough
        (zero added dispatches); with it on, each call abstracts its
        arguments and a fresh signature counts as a compilation (what
        it cost is the set-up account's to say)."""

        def observed(*args: Any, **kwargs: Any) -> Any:
            if Settings.PROFILING_ENABLED:
                fresh, n_sigs = self._note(
                    name, _abstract_signature(args, kwargs)
                )
                labels = {"fn": name}
                if fresh:
                    metrics.gauge(
                        "tpfl_compile_signatures", float(n_sigs), labels=labels
                    )
                    if n_sigs > 1:
                        metrics.counter("tpfl_recompiles_total", labels=labels)
                    self._maybe_warn_storm(name, n_sigs)
                else:
                    metrics.counter(
                        "tpfl_compile_signature_hits_total", labels=labels
                    )
            return fn(*args, **kwargs)

        # Keep the lowering escape hatch static analysis uses on raw
        # jitted fns (tests/test_profiling.py::
        # test_observatory_wrap_preserves_lowering_handle).
        lower = getattr(fn, "lower", None)
        if lower is not None:
            observed.lower = lower  # type: ignore[attr-defined]
        observed.__wrapped__ = fn  # type: ignore[attr-defined]
        return observed

    def _note(self, name: str, sig: tuple) -> tuple[bool, int]:
        with self._lock:
            seen = self._signatures.setdefault(name, set())
            if sig in seen:
                return False, len(seen)
            seen.add(sig)
            return True, len(seen)

    def _maybe_warn_storm(self, name: str, n_sigs: int) -> None:
        warn_at = max(2, int(Settings.PROFILING_RECOMPILE_WARN))
        if n_sigs < warn_at:
            return
        with self._lock:
            if name in self._warned:
                return
            self._warned.add(name)
        # Outside _lock: the ring and logger take their own locks.
        flight.record(
            PROFILING_RING,
            {
                "kind": "event",
                "name": "recompile_storm",
                "node": PROFILING_RING,
                "trace": "",
                "t": time.monotonic(),
                "fn": name,
                "signatures": n_sigs,
            },
        )
        from tpfl.management.logger import logger

        logger.warning(
            PROFILING_RING,
            f"Recompile storm: '{name}' compiled for {n_sigs} distinct "
            f"argument signatures (threshold "
            f"{warn_at}) — shape/dtype churn is defeating the jit cache",
        )

    def signature_counts(self) -> dict[str, int]:
        """fn name -> distinct abstract signatures seen (the recompile
        receipt of ``tests/test_elastic.py`` and ``chip_smoke.py``)."""
        with self._lock:
            return {k: len(v) for k, v in self._signatures.items()}

    def reset(self) -> None:
        with self._lock:
            self._signatures.clear()
            self._warned.clear()

    # --- the set-up account (jax.monitoring) ---

    def open_setup_account(self) -> None:
        """Start listening to jax's own monitoring events: the
        persistent compilation cache's answers and the three durations
        of every jit seam. Listeners are global and permanent in jax,
        so they install once: called where a process first means to
        compile (``FederationEngine.__init__``,
        :func:`ensure_compile_cache`); what jitted before that is not in
        the account."""
        with self._lock:
            if self._account_started is not None:
                return
            self._account_started = time.monotonic()
        self._process_started = process_started()
        import jax.monitoring as jmon

        jmon.register_event_listener(self._on_cache_event)
        jmon.register_event_duration_secs_listener(self._on_duration)

    def _on_cache_event(self, event: str, **kw: Any) -> None:
        # jax fires both in the compiling thread, just before that
        # program's backend_compile_duration.
        if event == _CACHE_HIT_EVENT:
            self._thread.hit = True
            # The cold-start receipt the compile cache is judged by.
            metrics.counter("tpfl_compile_cache_warm_total")
            with self._lock:
                self._cache["hits"] += 1
        elif event == _CACHE_MISS_EVENT:
            with self._lock:
                self._cache["misses"] += 1

    def _on_duration(self, event: str, duration: float, **kw: Any) -> None:
        """One compile event, O(1) amortised: every event is pushed
        once and popped at most once."""
        phase = _PHASE_OF_EVENT.get(event)
        if phase is None:
            field = _CACHE_SECONDS_OF_EVENT.get(event)
            if field is not None:
                with self._lock:
                    self._cache[field] += duration
            return
        # jax timed the span on time.time(); only its length is taken
        # from there, so the account stays on ONE clock.
        end = time.monotonic()
        start = end - duration
        thread = self._thread.__dict__
        if event == _BACKEND_EVENT and thread.pop("hit", False):
            phase = "load"  # fires on a hit too, holding the retrieval
        pending = thread.setdefault("pending", [])
        key = (phase, str(kw.get("fun_name", "")))
        with self._lock:
            # One thread's events are nested or disjoint, so what this
            # one contains is a suffix of ``pending``; the midpoint
            # decides, which a skew between the two clocks of up to
            # half the inner event's length cannot turn.
            while pending and pending[-1][0] > start:
                _, seconds, inner = pending.pop()
                row = self._by_name[inner]
                row[0] -= 1
                row[1] -= seconds
                row[2] += 1
                row[3] += seconds
            row = self._by_name.get(key)
            if row is None:
                row = self._by_name[key] = [0, 0.0, 0, 0.0]
            row[0] += 1
            row[1] += duration
        pending.append((end - duration / 2, duration, key))
        if len(pending) > _PENDING_CAP:
            del pending[: _PENDING_CAP // 2]
            with self._lock:
                self._frozen_events += _PENDING_CAP // 2

    def first_call(self, program: str, t0: float, t1: float) -> None:
        """One row a NEW program (or ``engine_init``): the wall of the
        call that traced and compiled it, on ``time.monotonic``. Less
        the program's own trace + lower + load + compile, it is what
        the caller spent dispatching and enqueueing a new program."""
        with self._lock:
            self._first_calls.append({"program": program, "t0": t0, "t1": t1})

    def setup_account(self) -> dict:
        """The account so far, as a plain snapshot (JSON-ready):

        - ``started`` / ``process_started``: when the account opened
          and when the process did, on ``time.monotonic`` (None where
          unknown);
        - ``phases[phase]``: ``seconds`` / ``events`` of OUTERMOST
          events — those no other compile event of the same thread
          contains, so the four phases never count a second twice —
          and ``nested_seconds`` / ``nested_events`` of the rest;
        - ``cache``: the persistent cache's ``hits`` / ``misses`` and
          jax's ``retrieval_seconds`` / ``saved_seconds``;
        - ``programs[name]``: ``seconds`` and ``events`` by phase of
          that program's outermost events (``tpfl_window``: the
          engine's window programs; the callers' own jits under theirs);
        - ``nested``: the names that cost most inside other events,
          as ``{phase, name, events, seconds}``;
        - ``first_calls``: :meth:`first_call`'s rows, oldest first;
        - ``frozen_events``: events taken as outermost unasked because
          a thread held ``_PENDING_CAP`` open questions (0 unless one
          event has tens of thousands of direct children)."""
        with self._lock:
            rows = [(key, tuple(row)) for key, row in self._by_name.items()]
            out: dict = {
                "started": self._account_started,
                "process_started": self._process_started,
                "cache": dict(self._cache),
                "first_calls": [dict(r) for r in self._first_calls],
                "frozen_events": self._frozen_events,
            }
        phases = {
            phase: {
                "seconds": 0.0, "events": 0,
                "nested_seconds": 0.0, "nested_events": 0,
            }
            for phase in SETUP_PHASES
        }
        programs: dict = {}
        nested = []
        for (phase, name), (events, seconds, n_events, n_seconds) in rows:
            total = phases[phase]
            if events:  # else the float left over from moving it out
                total["events"] += events
                total["seconds"] += seconds
                program = programs.setdefault(_program_name(name), {
                    "seconds": dict.fromkeys(SETUP_PHASES, 0.0),
                    "events": dict.fromkeys(SETUP_PHASES, 0),
                })
                program["seconds"][phase] += seconds
                program["events"][phase] += events
            if n_events:
                total["nested_events"] += n_events
                total["nested_seconds"] += n_seconds
                nested.append({
                    "phase": phase, "name": name,
                    "events": n_events, "seconds": n_seconds,
                })
        nested.sort(key=lambda r: (-r["seconds"], -r["events"], r["name"]))
        out.update(
            phases=phases, programs=programs, nested=nested[:_NESTED_TOP]
        )
        return out

    def publish(self, registry: Any) -> None:
        """Registry collector: the account's phase totals as
        ``tpfl_setup_seconds_total{phase}`` (outermost events) and
        ``tpfl_setup_events_total{phase,nested}``. Pull-style — the
        compile callbacks never touch the registry — adding what came
        since the last scrape (an event found to be nested after one
        moves between the two ``nested`` series)."""
        for phase, total in self.setup_account()["phases"].items():
            for name, labels, value in (
                ("tpfl_setup_seconds_total", {}, total["seconds"]),
                ("tpfl_setup_events_total", {"nested": "false"}, total["events"]),
                (
                    "tpfl_setup_events_total", {"nested": "true"},
                    total["nested_events"],
                ),
            ):
                series = (name, phase, labels.get("nested"))
                delta = value - self._published.get(series, 0.0)
                if delta:
                    registry.counter(
                        name, float(delta), labels={"phase": phase, **labels}
                    )
                    self._published[series] = value


# --- round profiler -------------------------------------------------------


class _RoundSpan:
    """Accumulating component timer (``with rounds.span(node, comp):``)."""

    __slots__ = ("_profiler", "_node", "_component", "_t0")

    def __init__(self, profiler: "RoundProfiler", node: str, component: str) -> None:
        self._profiler = profiler
        self._node = node
        self._component = component
        self._t0 = 0.0

    def __enter__(self) -> "_RoundSpan":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc: object) -> None:
        self._profiler.add(
            self._node, self._component, time.monotonic() - self._t0
        )


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: object) -> None:
        pass


_NULL_SPAN = _NullSpan()


class RoundProfiler:
    """Per-round wall-clock attribution.

    ``begin_round(node, round)`` opens a round window (the vote stage),
    instrumented sites accumulate seconds into named components
    (:data:`COMPONENTS`) via :meth:`add` / :meth:`span`, and
    ``end_round`` (the round-finished stage) closes the window:
    ``host_other`` is the residual (wall minus everything measured, so
    attribution can never silently drop time), per-component seconds
    land in ``tpfl_round_attr_seconds{node,component}`` histograms and
    a ``round`` span in the node's flight ring, and the completed
    record is retained for :meth:`attribution` (tests).

    Components may OVERLAP in wall time (an eager fold on a gRPC
    handler thread runs while the learning thread sits in the gossip
    wait), so the measured sum can exceed the wall; coverage is
    reported, not clamped. Everything is gated by
    ``Settings.PROFILING_ENABLED`` — off means no-op spans and zero
    bookkeeping.
    """

    def __init__(self) -> None:
        self._lock = make_lock("RoundProfiler._lock")
        # guarded-by: _lock. Per node a STACK of open round windows:
        # the free-running engine dispatches window N+1 (opening its
        # record) before closing window N's — overlapping windows under
        # one node tag are the pipelined steady state, not an error.
        self._active: dict[str, list[dict]] = {}
        # guarded-by: _lock
        self._done: deque = deque(maxlen=1024)

    def enabled(self) -> bool:
        return bool(Settings.PROFILING_ENABLED)

    def begin_round(self, node: str, round: "int | None") -> None:
        if not Settings.PROFILING_ENABLED:
            return
        with self._lock:
            self._active.setdefault(node, []).append({
                "node": node,
                "round": round if round is not None else -1,
                "t0": time.monotonic(),
                "parts": dict.fromkeys(
                    ("train", "dispatch", "fold", "gossip"), 0.0
                ),
            })

    def _open_record(
        self, node: str, round: "int | None"
    ) -> "dict | None":
        """The node's open record for ``round`` — the most recent one
        when ``round`` is None or unmatched (legacy single-window
        callers never pass an ordinal). Caller holds ``_lock``."""
        recs = self._active.get(node)
        if not recs:
            return None
        if round is not None:
            for rec in recs:
                if rec["round"] == round:
                    return rec
        return recs[-1]

    def add(
        self, node: str, component: str, seconds: float,
        round: "int | None" = None,
    ) -> None:
        """Accumulate measured seconds into the node's OPEN round (a
        no-op outside a round window — bare learner fits in tests don't
        need a federation round to exist). ``round`` disambiguates
        when several windows are in flight (the pipelined engine);
        None targets the most recently opened."""
        if not Settings.PROFILING_ENABLED or seconds <= 0:
            return
        with self._lock:
            rec = self._open_record(node, round)
            if rec is not None:
                parts = rec["parts"]
                parts[component] = parts.get(component, 0.0) + seconds

    def span(self, node: str, component: str) -> "_RoundSpan | _NullSpan":
        if not Settings.PROFILING_ENABLED:
            return _NULL_SPAN
        return _RoundSpan(self, node, component)

    def end_round(self, node: str, round: "int | None") -> "dict | None":
        if not Settings.PROFILING_ENABLED:
            return None
        now = time.monotonic()
        with self._lock:
            rec = self._open_record(node, round)
            if rec is not None:
                self._active[node].remove(rec)
                if not self._active[node]:
                    del self._active[node]
        if rec is None:
            return None
        wall = max(now - rec["t0"], 1e-9)
        parts = rec["parts"]
        measured = sum(parts.values())
        parts["host_other"] = max(0.0, wall - measured)
        record = {
            "node": node,
            "round": rec["round"],
            "wall": wall,
            "parts": parts,
            # components (incl. the residual) over wall: ~1.0 unless
            # concurrent components overlapped past the wall itself.
            "coverage": (measured + parts["host_other"]) / wall,
            "measured_frac": measured / wall,
        }
        with self._lock:
            self._done.append(record)
        for comp, secs in parts.items():
            metrics.observe(
                "tpfl_round_attr_seconds", secs,
                labels={"node": node, "component": comp},
                buckets=ROUND_BUCKETS,
            )
        metrics.observe(
            "tpfl_round_wall_seconds", wall,
            labels={"node": node}, buckets=ROUND_BUCKETS,
        )
        flight.record(
            node,
            {
                "kind": "span",
                "name": "round",
                "node": node,
                "trace": "",
                "t0": rec["t0"],
                "t1": now,
                "round": record["round"],
                **{f"s_{k}": round_(v) for k, v in parts.items()},
            },
        )
        return record

    def record_external(
        self, node: str, round: "int | None", parts: dict, wall: float
    ) -> "dict | None":
        """Append one COMPLETED round record whose component seconds
        were measured elsewhere — the engine-plane fan-out's per-round
        attribution (a device-side window's measured dispatch/train
        split divided over its rounds, ``tpfl.management.engine_obs``).
        Emits the same ``tpfl_round_attr_seconds`` histograms and
        flight ``round`` span as :meth:`end_round`; ``host_other`` is
        the residual exactly as there. Gated like every profiler tap."""
        if not Settings.PROFILING_ENABLED:
            return None
        wall = max(float(wall), 1e-9)
        parts = {k: float(v) for k, v in parts.items()}
        measured = sum(parts.values())
        parts.setdefault("host_other", max(0.0, wall - measured))
        record = {
            "node": node,
            "round": int(round) if round is not None else -1,
            "wall": wall,
            "parts": parts,
            "coverage": sum(parts.values()) / wall,
            "measured_frac": measured / wall,
            # Distinguishes replayed rows (engine fan-out) from rounds
            # this profiler timed itself.
            "external": True,
        }
        with self._lock:
            self._done.append(record)
        for comp, secs in parts.items():
            metrics.observe(
                "tpfl_round_attr_seconds", secs,
                labels={"node": node, "component": comp},
                buckets=ROUND_BUCKETS,
            )
        metrics.observe(
            "tpfl_round_wall_seconds", wall,
            labels={"node": node}, buckets=ROUND_BUCKETS,
        )
        now = time.monotonic()
        flight.record(
            node,
            {
                "kind": "span",
                "name": "round",
                "node": node,
                "trace": "",
                "t0": now - wall,
                "t1": now,
                "round": record["round"],
                **{f"s_{k}": round_(v) for k, v in parts.items()},
            },
        )
        return record

    def attribution(self, node: "str | None" = None) -> list[dict]:
        """Completed round records (optionally one node's), oldest
        first — the test surface."""
        with self._lock:
            records = list(self._done)
        if node is not None:
            records = [r for r in records if r["node"] == node]
        return records

    def reset(self) -> None:
        with self._lock:
            self._active.clear()
            self._done.clear()


def round_(v: float, nd: int = 6) -> float:
    """round() under a name that doesn't shadow the round kwargs used
    throughout the profiler API."""
    return round(v, nd)


# --- wall timers ----------------------------------------------------------


def measure_dispatch_rtt(best_of: int = 3) -> float:
    """Seconds for one dispatch+sync round trip of a trivially small
    jitted program (best of ``best_of`` after a discarded compile run).
    When it is the same order as a federated round, host-loop timing
    misattributes it to the device; what it is on the current chip host
    is printed by ``chip_smoke.py``'s ``sync`` phase."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def empty_call(x):
        return lax.fori_loop(0, 100, lambda i, a: a + x * (1 + i), jnp.float32(0))

    # Nothing is donated, so the "rebind" hands the same scalar back.
    rtt, _ = best_of_wall_donated(
        empty_call, (jnp.float32(1),), lambda out, args: args, best_of
    )
    return rtt


def _sync_scalar(out: Any) -> None:
    """The one host sync the wall timers share: copy 4 bytes of the
    LAST output leaf (perf_cnn.md round-5 trap #1 — syncing by copying
    an array carry measures the device-to-host transfer, not the
    device)."""
    import jax
    import numpy as np

    float(np.asarray(jax.tree_util.tree_leaves(out)[-1]).ravel()[0])


def best_of_wall_donated(
    fn: Callable,
    args: tuple,
    rebind: Callable[[Any, tuple], tuple],
    n: int = 3,
) -> tuple[float, Any]:
    """Best-of-n wall time of ``fn(*args)`` (scalar host sync on the
    last output leaf, first call a discarded compile/warm run) for a
    program that DONATES input buffers: each call consumes (part of)
    its arguments, so iterations cannot re-feed ``args`` verbatim —
    ``rebind(last_outputs, prev_args) -> args`` re-materializes the
    consumed inputs for the next iteration, typically by threading the
    program's own outputs back in (the production shape: window N+1
    trains from window N's fold, e.g. ``lambda out, a: (out[0],
    *a[1:])``). Rebinding and buffer materialization happen OUTSIDE the
    timed region (``block_until_ready`` before the clock starts), so
    the measured wall is the donating program itself. Returns
    ``(best_seconds, last_outputs)``."""
    import jax

    out = fn(*args)  # compile + warm (consumes the caller's buffers)
    _sync_scalar(out)
    best = float("inf")
    for _ in range(max(1, n)):
        args = rebind(out, args)
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        out = fn(*args)
        _sync_scalar(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


# --- cost model -----------------------------------------------------------


class CostModel:
    """Unified FLOPs / MFU accounting — the ONE ``cost_analysis()``
    call path, shared with ``parallel/scaling.py:analyze_compiled``,
    so static scaling analysis and live MFU can never disagree."""

    @staticmethod
    def cost_analysis(compiled: Any) -> dict:
        """XLA's cost analysis dict for a compiled executable."""
        return dict(compiled.cost_analysis() or {})

    @classmethod
    def xla_flops(cls, compiled: Any) -> "float | None":
        """XLA's flop count for an already-compiled executable.
        Caveat (the one copy of it): a ``lax.scan``/``fori_loop`` body
        is counted ONCE regardless of trip count — callers must scale
        by the number of steps themselves."""
        try:
            return float(cls.cost_analysis(compiled).get("flops", 0.0)) or None
        except Exception:
            return None

    # --- analytic model flops (immune to scan-once counting and to
    # custom-VJP lowering; derived from the zoo modules' actual config
    # so a model change can never silently desynchronize MFU) ---

    @staticmethod
    def analytic_fwd_mults(
        module: Any, input_shape: tuple[int, ...]
    ) -> "int | None":
        """Per-sample forward multiply count for the zoo architectures
        (2x per mult = FLOPs). Supports the zoo ``CNN`` (3x3 SAME
        convs + 2x2 max-pool + dense head), ``MLP`` (dense stack) and
        ``TransformerLM`` (per token per layer: QKV 3d² + attn-out d²
        + FFN 2·ratio·d² mults plus causal attention ≈ S·d for the
        score and value matmuls over ~S/2 visible keys; plus the d·V
        logits head — the PaLM-appendix accounting, embeddings are
        lookups); returns None for architectures without an analytic
        model — callers fall back to :meth:`xla_flops`."""
        vocab = getattr(module, "vocab", None)
        t_dim = getattr(module, "dim", None)
        t_layers = getattr(module, "n_layers", None)
        if vocab is not None and t_dim is not None and t_layers is not None:
            if len(input_shape) != 1:
                return None
            s = int(input_shape[0])
            ratio = int(getattr(module, "mlp_ratio", 4))
            per_token = (
                t_layers * ((4 + 2 * ratio) * t_dim * t_dim + s * t_dim)
                + t_dim * vocab
            )
            return int(s * per_token)
        channels = getattr(module, "channels", None)
        dense = getattr(module, "dense", None)
        out_channels = getattr(module, "out_channels", None)
        hidden = getattr(module, "hidden_sizes", None)
        if channels is not None and dense is not None and out_channels is not None:
            if len(input_shape) != 3:
                return None
            h, w, cin = input_shape
            mults = 0
            for c in channels:
                mults += h * w * 9 * cin * c  # 3x3 SAME conv
                cin = c
                h //= 2
                w //= 2  # 2x2 max-pool
            mults += (h * w * cin) * dense
            mults += dense * out_channels
            return int(mults)
        if hidden is not None and out_channels is not None:
            features = 1
            for d in input_shape:
                features *= d
            mults = 0
            for width in tuple(hidden) + (out_channels,):
                mults += features * width
                features = width
            return int(mults)
        return None

    @classmethod
    def analytic_train_flops(
        cls, module: Any, input_shape: tuple[int, ...], samples: int
    ) -> "float | None":
        """Model FLOPs of training on ``samples`` samples: 2 FLOPs per
        mult, x3 for forward+backward."""
        mults = cls.analytic_fwd_mults(module, input_shape)
        if mults is None:
            return None
        return 3.0 * 2.0 * mults * samples

    # --- MFU ---

    @staticmethod
    def mfu(
        flops_per_sec: float,
        device: Any = None,
        n_chips: int = 1,
    ) -> "float | None":
        """Model-FLOPs utilization against the device's peak (None when
        the device kind has no published peak — CPU CI runs)."""
        if device is None:
            import jax

            device = jax.devices()[0]
        peak = peak_flops(device)
        if not peak:
            return None
        return flops_per_sec / (peak * max(1, n_chips))

    @classmethod
    def record_round(
        cls,
        program: str,
        flops: float,
        seconds: float,
        device: Any = None,
        n_chips: int = 1,
    ) -> "float | None":
        """Publish one round's live MFU: ``tpfl_mfu{program}`` /
        ``tpfl_round_flops{program}`` gauges plus the per-round seconds
        histogram. Returns the MFU (None off-TPU). ``seconds`` is HOST
        wall time (ROADMAP D7): the benchmark's ``mfu_device_pct``
        divides by device time instead."""
        seconds = max(seconds, 1e-12)
        value = cls.mfu(flops / seconds, device=device, n_chips=n_chips)
        metrics.gauge(
            "tpfl_round_flops", float(flops), labels={"program": program}
        )
        metrics.observe(
            "tpfl_round_compute_seconds", seconds,
            labels={"program": program}, buckets=ROUND_BUCKETS,
        )
        if value is not None:
            metrics.gauge("tpfl_mfu", float(value), labels={"program": program})
        return value


# --- HBM high-water marks -------------------------------------------------


class HbmTracker:
    """Per-device HBM gauges with a process-lifetime HIGH-WATER MARK.

    ``node_monitor`` samples on its cadence; the tracker is also a
    registry collector so a scrape/dump observes fresh values even
    with no monitor running. TPU runtimes report
    ``peak_bytes_in_use`` themselves where available; the tracker
    additionally maxes over its own samples so backends that only
    report ``bytes_in_use`` still get a peak."""

    def __init__(self) -> None:
        self._lock = make_lock("HbmTracker._lock")
        # guarded-by: _lock
        self._peaks: dict[str, float] = {}

    def sample(self) -> list[tuple[str, float, float]]:
        """[(device_id, bytes_in_use, peak_bytes)] for every local
        device exposing ``memory_stats``; updates the registry gauges
        (``tpfl_hbm_bytes_in_use`` / ``tpfl_hbm_peak_bytes``, and
        ``tpfl_hbm_peak_bytes_reserved`` where the backend reports
        ``peak_bytes_reserved``; labeled by device). Host-side reads
        only — zero device dispatches."""
        if "jax" not in sys.modules:
            return []  # never the import that drags a backend in
        out: list[tuple[str, float, float]] = []
        try:
            import jax

            for d in jax.local_devices():
                stats_fn = getattr(d, "memory_stats", None)
                if stats_fn is None:
                    continue
                try:
                    stats = stats_fn()
                except Exception:
                    continue
                if not stats or "bytes_in_use" not in stats:
                    continue
                out.append(self._record(str(d.id), stats))
        except Exception:
            return out
        return out

    def _record(self, dev: str, stats: dict) -> tuple[str, float, float]:
        in_use = float(stats["bytes_in_use"])
        reported_peak = float(stats.get("peak_bytes_in_use", 0.0))
        with self._lock:
            peak = max(self._peaks.get(dev, 0.0), in_use, reported_peak)
            self._peaks[dev] = peak
        labels = {"device": dev}
        metrics.gauge("tpfl_hbm_bytes_in_use", in_use, labels=labels)
        metrics.gauge("tpfl_hbm_peak_bytes", peak, labels=labels)
        if "peak_bytes_reserved" in stats:
            # What a program RESERVED at its largest, temporaries
            # included — several times the live arrays above, and the
            # figure that decides whether a federation fits the chip.
            metrics.gauge(
                "tpfl_hbm_peak_bytes_reserved",
                float(stats["peak_bytes_reserved"]), labels=labels,
            )
        return dev, in_use, peak

    def observe(self, dev: str, stats: dict) -> tuple[str, float, float]:
        """Fold one externally-sampled ``memory_stats`` dict (tests /
        exotic backends) through the same peak tracking."""
        return self._record(dev, stats)

    def peaks(self) -> dict[str, float]:
        with self._lock:
            return dict(self._peaks)

    def reset(self) -> None:
        with self._lock:
            self._peaks.clear()


# --- compiled-program cache visibility (pull-style collector) ------------


def _compiled_cache_collector(registry: Any) -> None:
    """Registry collector: sizes of the process-lifetime compiled
    program caches (``jax_learner._SHARED_PROGRAMS`` / ``_TX_CACHE``,
    ``batched_fit._programs`` + per-program shape caches). Reads ONLY
    modules already imported (``sys.modules`` peek — a metrics scrape
    must never be the thing that imports the learning stack)."""
    jl = sys.modules.get("tpfl.learning.jax_learner")
    if jl is not None:
        registry.gauge(
            "tpfl_compiled_cache_entries",
            float(len(jl._SHARED_PROGRAMS)),
            labels={"cache": "shared_programs"},
        )
        registry.gauge(
            "tpfl_compiled_cache_entries",
            float(len(jl._TX_CACHE)),
            labels={"cache": "tx"},
        )
    bf = sys.modules.get("tpfl.simulation.batched_fit")
    if bf is not None:
        programs = list(bf._programs.values())
        registry.gauge(
            "tpfl_compiled_cache_entries",
            float(len(programs)),
            labels={"cache": "batched_programs"},
        )
        registry.gauge(
            "tpfl_compiled_cache_entries",
            float(sum(len(p._fns) for p in programs)),
            labels={"cache": "batched_shape_fns"},
        )


def _hbm_collector(registry: Any) -> None:
    hbm.sample()


# --- jax.profiler trace wrap (any run) -----------------------------------

_trace_lock = make_lock("profiling._trace_lock")
_trace_dir: "list[str]" = []  # 0- or 1-element; guarded by _trace_lock


def start_trace(directory: str) -> bool:
    """Start a process-wide ``jax.profiler`` trace into ``directory``
    (idempotent: a second start while one is active is a no-op —
    several in-process nodes share one profiler). Returns True when
    this call actually started it."""
    if not directory:
        return False
    with _trace_lock:
        if _trace_dir:
            return False
        _trace_dir.append(directory)
    try:
        import jax

        jax.profiler.start_trace(directory)
        return True
    except Exception as e:
        with _trace_lock:
            _trace_dir.clear()
        from tpfl.management.logger import logger

        logger.warning(PROFILING_RING, f"jax.profiler trace failed: {e}")
        return False


def stop_trace() -> bool:
    """Stop the active trace, if any (idempotent)."""
    with _trace_lock:
        if not _trace_dir:
            return False
        directory = _trace_dir.pop()
    try:
        import jax

        jax.profiler.stop_trace()
        from tpfl.management.logger import logger

        logger.info(
            PROFILING_RING,
            f"jax.profiler trace written to {directory} "
            "(view with TensorBoard/xprof)",
        )
        return True
    except Exception:
        return False


@contextlib.contextmanager
def maybe_trace(directory: "str | None") -> Iterator[None]:
    """Wrap a block in a jax profiler trace when ``directory`` is
    set; a shared no-op otherwise (the CLI's ``experiment run
    --profile`` rides this)."""
    started = start_trace(directory) if directory else False
    try:
        yield
    finally:
        if started:
            stop_trace()


# The directory the persistent compilation cache is armed at (None until
# ensure_compile_cache runs — jax config is process-global, so this
# module remembers what it already applied).
# unguarded: written from single-threaded set-up paths (entry points,
# the engine constructor); a racy double-write applies the same
# jax.config.update twice, which is idempotent.
_COMPILE_CACHE_DIR: "str | None" = None

#: The environment variable JAX itself reads for its persistent cache.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(directory: "str | None" = None) -> str:
    """THE compile-cache rule, in one place. In order:

    1. ``JAX_COMPILATION_CACHE_DIR`` set -> that directory. JAX already
       has it; nothing in this repo re-points it (not an explicit
       ``directory``, not ``Settings.COMPILE_CACHE_DIR``).
    2. an explicit ``directory`` (``Settings.COMPILE_CACHE_DIR``, a
       test's private ``tmp_path``).
    3. the fixed ``<checkout>/.jax_cache`` — never a tempfile, pid or
       time-derived path: the path is part of the cache key, so a
       directory that moves never hits."""
    import os

    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return env
    if directory:
        return os.path.abspath(directory)
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def ensure_compile_cache(directory: "str | None" = None) -> str:
    """Arm JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory. Called by
    every entry point (``chip_smoke.py``, the examples)
    and by the engine constructor when ``Settings.COMPILE_CACHE_DIR``
    is set. Idempotent per directory. A warm process then replays
    lowered programs from disk instead of recompiling — the
    ``tpfl_compile_cache_warm_total`` counter (fed ungated from jax's
    ``/jax/compilation_cache/cache_hits`` monitoring event) is the
    receipt. Failure to arm RAISES: a run that believes it is cached
    and is not compiles everything, every call."""
    import os

    import jax  # lazy: the management layer stays backend-free

    global _COMPILE_CACHE_DIR
    d = compile_cache_dir(directory)
    if _COMPILE_CACHE_DIR == d:
        return d
    # Cache EVERYTHING: tpfl's engine programs are few and large, and
    # the default 1 s compile-time floor would skip the small per-tier
    # variants the elastic engine compiles. Thresholds, not a directory
    # — safe to set whoever placed the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not os.environ.get(COMPILE_CACHE_ENV):
        from jax.experimental.compilation_cache import (
            compilation_cache as _jax_cc,
        )

        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        # jax initializes its cache object ONCE per process, at the
        # first compile — and callers may have compiled (placement
        # jits, another directory) before this point. Kick it back to
        # uninitialized so the next compile binds the directory set
        # above. Only on this branch: a directory that came from the
        # environment was bound by jax itself and is never re-pointed.
        _jax_cc.reset_cache()
    _COMPILE_CACHE_DIR = d
    # The listener that counts warm hits (and keeps the set-up account)
    # exists from here on, engine or no engine.
    observatory.open_setup_account()
    return d


#: Process-wide singletons (one federation per process in every
#: simulation mode — same scope rationale as telemetry.metrics/flight).
observatory = CompileObservatory()
rounds = RoundProfiler()
cost_model = CostModel()
hbm = HbmTracker()

metrics.register_collector(_compiled_cache_collector)
metrics.register_collector(_hbm_collector)
metrics.register_collector(observatory.publish)
