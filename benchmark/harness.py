"""One cell, end to end: build the federation on the device from the
seed, warm up, measure, trace, check against the plain reference.

The system under test is reached only through its normal entry points
(``FederationEngine``, ``dispatch_window`` / ``EngineWindow``,
``WindowPipeline.run``, ``Settings``); timing, spans, the compile
count and the comparison that decides ``correct`` are the benchmark's
own. ``run.py`` is the command; tests call :func:`run_cell` with the
virtual CPU devices and toy sizes.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Optional

from benchmark import trace_reduce
from benchmark.cells import ROOT, Cell, load_reader

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Windows in the traced slice of a pipeline cell, and the bounds of a
#: sequential cell's (about TRACE_SLICE_S seconds of it).
TRACE_PIPELINE_WINDOWS = 3
TRACE_SLICE_S = 3.0
TRACE_SEQUENTIAL_WINDOWS = (3, 30)

class CompileMeter:
    """Counts what JAX's own monitoring says about compilation: backend
    compiles, persistent-cache hits and misses, and seconds spent
    tracing + lowering + compiling. (Copied from ``chip_smoke.py``: the
    yardstick lives with the benchmark.)"""

    def __init__(self) -> None:
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.seconds = 0.0

    def install(self) -> "CompileMeter":
        import jax.monitoring as jmon

        def on_event(event: str, **kw: Any) -> None:
            if event.endswith("/compilation_cache/cache_hits"):
                self.hits += 1
            elif event.endswith("/compilation_cache/cache_misses"):
                self.misses += 1

        def on_duration(event: str, duration: float, **kw: Any) -> None:
            if event.startswith("/jax/core/compile/"):
                self.seconds += float(duration)
                if event.endswith("/backend_compile_duration"):
                    self.compiles += 1

        jmon.register_event_listener(on_event)
        jmon.register_event_duration_secs_listener(on_duration)
        return self


class Spans:
    """The benchmark's own spans around its calls into the engine:
    ``(name, start_s, end_s)`` on ``time.perf_counter``. While a trace
    is being taken each span is also a ``jax.profiler.TraceAnnotation``
    named ``bench:<name>``, which puts it on the device trace's clock."""

    def __init__(self) -> None:
        self.records: list = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        with contextlib.ExitStack() as stack:
            if self.annotate:
                import jax

                stack.enter_context(
                    jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)
                )
            start = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, start, time.perf_counter()))

    def durations(self, name: str) -> list:
        return [end - start for n, start, end in self.records if n == name]


def arm_compile_cache(root: Path = ROOT) -> str:
    """The program's own rule (``profiling.compile_cache_dir``), copied:
    ``JAX_COMPILATION_CACHE_DIR`` if the machine sets it, else the fixed
    ``<checkout>/.jax_cache``. Everything is cached, however quick to
    compile: a warm run must find every program."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    directory = os.environ.get(COMPILE_CACHE_ENV)
    if not directory:
        directory = str(root / ".jax_cache")
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    return directory


@dataclass
class Federation:
    """A cell's federation on the device: engine, state, data."""

    engine: Any
    pipeline: Any
    params: Any
    aux: Any  # None for a model with no state besides its parameters
    xs: Any
    ys: Any
    window: int
    window_estimate_s: float = 0.0


@dataclass
class WindowLog:
    """What a driver saw: windows dispatched, rounds completed, seconds
    from the first dispatch to the last result, per-window seconds
    (sequential driver only), the last window's mean loss, and the
    windows that returned a non-finite loss."""

    windows: int = 0
    rounds: int = 0
    seconds: float = 0.0
    window_s: list = field(default_factory=list)
    last_loss: float = math.nan
    failed: int = 0


def init_state(engine: Any, shape: tuple, seed: int) -> tuple:
    """``engine.init_state`` in ONE jitted call that writes the stacked
    state where the engine wants it (same key, same initialisers: the
    values differ from the op-by-op ones in the last bit at most, and a
    test holds it to that)."""
    import jax
    import jax.numpy as jnp

    from tpfl.parallel.mesh import federation_sharding

    dummy = jnp.zeros(
        (1, *shape), getattr(engine.module, "input_dtype", jnp.float32)
    )

    def make(key):
        variables = engine.module.init(key, dummy, train=False)
        aux = {k: v for k, v in variables.items() if k != "params"}
        return (
            engine.broadcast_params(variables["params"]),
            engine.broadcast_params(aux),
        )

    sharding = None if engine.mesh is None else federation_sharding(engine.mesh)
    return jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))


def build_federation(
    cell: Cell, traffic: dict, seed: int, devices: list, variant: bool = True
) -> Federation:
    """The federation of ``traffic`` (the cell's own, or its ``check``
    sizes) on ``devices``. ``variant`` False builds the plain dense,
    telemetry-off program whatever the cell's traffic says — what the
    reference describes."""
    import jax

    from tpfl.parallel import FederationEngine, create_mesh
    from tpfl.parallel.mesh import federation_sharding
    from tpfl.parallel.window_pipeline import WindowPipeline
    from tpfl.settings import Settings

    Settings.ENGINE_DONATE = True
    Settings.ENGINE_TELEMETRY = bool(variant and cell.traffic["telemetry"])
    Settings.ENGINE_WIRE_CODEC = cell.traffic["codec"] if variant else "dense"
    mesh = None
    if cell.traffic["mesh"]:
        mesh = create_mesh(dict(cell.traffic["mesh"]), devices=devices)
    engine = FederationEngine(
        cell.model.build_module(cell.config), int(traffic["nodes"]), mesh=mesh,
        learning_rate=float(cell.config["learning_rate"]), seed=seed,
    )
    params, aux = init_state(
        engine, cell.model.input_shape(cell.config, traffic), seed
    )
    sharding = None if mesh is None else federation_sharding(mesh)
    xs, ys = jax.jit(
        lambda key: cell.model.make_data(key, cell.config, traffic),
        out_shardings=sharding,
    )(jax.random.fold_in(jax.random.PRNGKey(seed), 1))
    xs, ys = engine.shard_data(xs, ys)
    return Federation(
        engine, WindowPipeline(engine), params, aux or None, xs, ys,
        int(cell.traffic["window"]),
    )


# --- drivers -----------------------------------------------------------------


def _mean_loss(engine: Any, losses: Any) -> float:
    import numpy as np

    return float(np.asarray(engine.unpad(losses), np.float32).mean())


def _carry(fed: Federation, result: tuple) -> Any:
    """Chain a window's result into the next; returns its losses."""
    fed.params = result[0]
    if fed.aux is not None:
        fed.aux = result[1]
    return result[-1]


def run_pipeline(
    fed: Federation, spans: Spans, n_windows: Optional[int] = None,
    seconds: Optional[float] = None,
) -> WindowLog:
    """``WindowPipeline.run`` over a FIXED number of windows, donation
    on. Asked for ``seconds``, the count is what the warm-up's window
    time says fits: the pipeline dispatches ahead of the device (its
    ``finalize`` does not wait when telemetry is off), so a deadline
    polled between dispatches would fire after the queue is already
    seconds deep."""
    import jax

    if n_windows is None:
        n_windows = max(2, int(seconds / fed.window_estimate_s))
    start = time.perf_counter()
    with spans("pipeline_run"):
        result, done = fed.pipeline.run(
            fed.params, fed.xs, fed.ys, aux=fed.aux,
            n_rounds=n_windows * fed.window, window=fed.window, donate=True,
        )
    with spans("block"):
        jax.block_until_ready(result)
    elapsed = time.perf_counter() - start
    with spans("read_result"):
        loss = _mean_loss(fed.engine, _carry(fed, result))
    windows = fed.pipeline.windows_run
    # Only the last window's losses come back; a non-finite value
    # anywhere before it is carried to the end by the model.
    return WindowLog(
        windows=windows, rounds=done, seconds=elapsed, last_loss=loss,
        failed=0 if math.isfinite(loss) else windows,
    )


def run_sequential(
    fed: Federation, spans: Spans, n_windows: Optional[int] = None,
    seconds: Optional[float] = None,
) -> WindowLog:
    """Per window: ``dispatch_window``, ``wait()``, ``finalize()`` —
    the pair ``run_rounds`` is — then the losses read on the host, as a
    researcher who logs every round does."""
    log = WindowLog()
    start = time.perf_counter()
    while (
        log.windows < n_windows if n_windows is not None
        else time.perf_counter() - start < seconds
    ):
        t0 = time.perf_counter()
        with spans("dispatch"):
            handle = fed.engine.dispatch_window(
                fed.params, fed.xs, fed.ys, aux=fed.aux,
                n_rounds=fed.window, donate=True,
            )
        with spans("block"):
            handle.wait()
        with spans("finalize"):
            result = handle.finalize()
        with spans("read_result"):
            log.last_loss = _mean_loss(fed.engine, _carry(fed, result))
        log.window_s.append(time.perf_counter() - t0)
        log.windows += 1
        log.rounds += fed.window
        log.failed += 0 if math.isfinite(log.last_loss) else 1
    log.seconds = time.perf_counter() - start
    return log


DRIVERS: dict = {"pipeline": run_pipeline, "sequential": run_sequential}


# --- measurement -------------------------------------------------------------


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (no interpolation: a reported tail is a
    window that happened)."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, math.ceil(q * len(ordered)) - 1))]


def memory_peaks(devices: list) -> dict:
    """Max over the cell's devices of the backend's two high-water
    marks. ``peak_bytes_reserved`` holds a program's temporaries;
    ``peak_bytes_in_use`` only live arrays (PERF.md §7)."""
    stats = [d.memory_stats() or {} for d in devices]
    return {
        key: max(int(s.get(key, 0)) for s in stats)
        for key in ("peak_bytes_reserved", "peak_bytes_in_use", "bytes_limit")
    }


def traced_slice(
    fed: Federation, driver: Callable, driver_name: str, trace_dir: Path
) -> tuple:
    """Run a short steady slice under the profiler; returns (the
    reduction of its trace or None, the rounds in it)."""
    import jax

    if driver_name == "pipeline":
        n = TRACE_PIPELINE_WINDOWS
    else:
        lo, hi = TRACE_SEQUENTIAL_WINDOWS
        n = min(hi, max(lo, int(TRACE_SLICE_S / fed.window_estimate_s)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # spans are ours; no per-call events
    spans = Spans()
    spans.annotate = True
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        log = driver(fed, spans, n_windows=n)
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.load_events(trace_reduce.find_xplane(str(trace_dir)))
    with open(trace_dir / "inventory.json", "w") as f:
        json.dump(trace_reduce.inventory(events), f)
    return trace_reduce.reduce_trace(events), log.rounds


def _update_error(sys_tree: Any, ref_tree: Any, base_tree: Any) -> tuple:
    """How far the engine's UPDATE is from the reference's:
    ``||(sys - base) - (ref - base)|| / ||ref - base||`` over all leaves,
    and the three leaves that carry most of the squared error with their
    own ratios. Reduced on the device (a model is hundreds of MB); only
    the per-leaf sums come to the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(sys_leaf, ref_leaf, base_leaf):
        f32 = jnp.float32
        want = ref_leaf.astype(f32) - base_leaf.astype(f32)
        miss = (sys_leaf.astype(f32) - base_leaf.astype(f32)) - want
        return jnp.sum(miss * miss), jnp.sum(want * want)

    paths = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(ref_tree)[0]
    ]
    rows = [
        (path, *map(float, sums(a, b, c)))
        for path, a, b, c in zip(
            paths, *(jax.tree_util.tree_leaves(t) for t in (sys_tree, ref_tree, base_tree))
        )
    ]
    miss, want = sum(r[1] for r in rows), sum(r[2] for r in rows)
    worst = [
        [path, math.sqrt(m / max(w, 1e-30)), m / max(miss, 1e-30)]
        for path, m, w in sorted(rows, key=lambda r: -r[1])[:3]
    ]
    return math.sqrt(miss / max(want, 1e-30)), worst


def check_against_reference(cell: Cell, seed: int, devices: list) -> dict:
    """One federated round of the cell's small seeded ``check``
    federation through ``FederationEngine`` against the configuration's
    plain reference, at the configuration's own widths: per-node losses
    and the folded model (and running statistics). Uneven weights, so
    that a fold that ignored them would fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    traffic = cell.traffic["check"]
    fed = build_federation(cell, traffic, seed + 1, devices, variant=False)
    n = int(traffic["nodes"])
    weights = jnp.arange(1, n + 1, dtype=jnp.float32)

    def on_first_device(tree, take=lambda x: x):
        # Copies, on the reference's one device: nothing of the engine's
        # (possibly sharded) buffers is shared with the reference.
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(take(x), devices[0]), tree
        )

    row0 = lambda x: x[0]  # noqa: E731  every row holds the global model
    params0 = on_first_device(fed.params, row0)
    aux0 = on_first_device(fed.aux or {}, row0)
    xs, ys = on_first_device((fed.xs, fed.ys), fed.engine.unpad)
    result = fed.engine.run_rounds(
        fed.params, fed.xs, fed.ys, weights=weights, aux=fed.aux,
        n_rounds=1, donate=False,
    )
    sys_losses = np.asarray(fed.engine.unpad(result[-1]), np.float64)
    sys_params = on_first_device(result[0], row0)
    sys_aux = on_first_device(result[1], row0) if fed.aux is not None else {}
    del fed, result
    ref_losses, ref_params, ref_aux = cell.model.reference_round(
        cell.config, params0, aux0, xs, ys, weights,
        float(cell.config["learning_rate"]),
    )
    ref_losses = np.asarray(ref_losses, np.float64)
    tol = cell.model.CHECK_TOLERANCES
    loss_err = float(np.max(np.abs(sys_losses - ref_losses) / np.abs(ref_losses)))
    update_err, worst = _update_error(sys_params, ref_params, params0)
    out = {
        "nodes": n,
        "losses_engine": sys_losses.tolist(),
        "losses_reference": ref_losses.tolist(),
        "loss_rel_err": loss_err,
        "update_rel_err": update_err,
        "update_worst_leaves": worst,
        "tolerances": tol,
    }
    ok = loss_err <= tol["loss"] and update_err <= tol["update"]
    if aux0:
        out["aux_rel_err"], _ = _update_error(sys_aux, ref_aux, aux0)
        ok = ok and out["aux_rel_err"] <= tol["aux"]
    out["agrees"] = bool(ok and np.isfinite(sys_losses).all())
    return out


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, devices: list,
    device: dict, peaks: dict, started: float, meter: CompileMeter,
    out_dir: Path, emit: Callable[[str], None] = print,
) -> dict:
    """Warm up, measure, trace if asked, check; returns the result
    line as a dict. ``started`` is the process's start on
    ``time.perf_counter``; ``emit`` takes the earlier, informative
    lines."""
    traffic, model = cell.traffic, cell.model
    driver = DRIVERS[traffic["driver"]]
    spans = Spans()
    fed = build_federation(cell, traffic, seed, devices)

    # Warm-up, which is also the loss check: loss_rounds rounds from the
    # seeded initial model through the cell's own windows. The engine
    # returns a window's LAST round of losses, so "the round-0 loss" is
    # read as the first window's.
    first = driver(fed, spans, n_windows=1)
    n_warm = traffic["loss_rounds"] // fed.window - 1
    warm = driver(fed, spans, n_windows=n_warm)
    fed.window_estimate_s = warm.seconds / n_warm
    loss_first, loss_at_k = first.last_loss, warm.last_loss

    compiles_before = meter.compiles
    spans.records.clear()
    setup_s = time.perf_counter() - started
    log = driver(fed, spans, seconds=seconds)
    compiles_in_window = meter.compiles - compiles_before
    memory = memory_peaks(devices)
    measured_at = time.perf_counter() - started

    elapsed_rate = log.rounds / log.seconds
    # Windows timed one by one on the host: the rate AT THE MEDIAN
    # WINDOW. Over ~150 windows the mean moves 0.7% from run to run on
    # stalls of a few windows (PERF.md, PR 22), five times the median's
    # spread; the tail is window_ms_p90's. A free-running pipeline has
    # no per-window times: rounds completed over seconds elapsed.
    rounds_per_s = (
        fed.window / percentile(log.window_s, 0.5) if log.window_s
        else elapsed_rate
    )
    per_round = model.samples_per_round(traffic)
    flops_per_round = 6.0 * model.fwd_mults_per_sample(cell.config, traffic) * per_round
    emit(json.dumps({
        "info": "window", "workload": cell.name, "seed": seed,
        "setup_s": setup_s, "windows": log.windows, "rounds": log.rounds,
        "seconds": log.seconds, "rounds_per_s": rounds_per_s,
        "rounds_per_s_elapsed": elapsed_rate,
        f"{model.SAMPLE_UNIT}_per_s_per_chip": rounds_per_s * per_round / cell.chips,
        "model_flops_per_round": flops_per_round,
        "window_estimate_s": fed.window_estimate_s,
        "window_ms_median": (
            percentile(log.window_s, 0.5) * 1e3 if log.window_s else None
        ),
        "loss_first_window": loss_first, "loss_at_k": loss_at_k,
        "loss_last": log.last_loss, "memory": memory,
        "compile": {
            "in_window": compiles_in_window, "total": meter.compiles,
            "cache_hits": meter.hits, "cache_misses": meter.misses,
            "seconds": meter.seconds,
        },
    }))

    values = {
        "rounds_per_s": rounds_per_s,
        "peak_hbm_gb": memory["peak_bytes_reserved"] / 1e9,
        "loss_at_k": loss_at_k,
        "setup_s": setup_s,
    }
    if log.window_s:
        values["window_ms_p90"] = percentile(log.window_s, 0.9) * 1e3

    device = dict(device, memory_peak_bytes=memory["peak_bytes_reserved"])
    result: dict = {}
    if trace:
        reduced, rounds = traced_slice(
            fed, driver, traffic["driver"], out_dir / "trace"
        )
        obs = {
            "spans": spans, "trace": reduced, "trace_rounds": rounds,
            "compiles_in_window": compiles_in_window, "memory": memory,
            "flops_per_round": flops_per_round, "chips": cell.chips,
            "peaks": peaks,
        }
        values = {}
        for metric in cell.per_layer:
            value = load_reader(metric["name"])(obs)
            if value is not None:
                values[metric["name"]] = value
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
        listed = cell.per_layer
    else:
        listed = cell.end_to_end

    del fed.params, fed.aux, fed.xs, fed.ys
    gc.collect()
    checked_from = time.perf_counter() - started
    check = check_against_reference(cell, seed, devices)
    emit(json.dumps({
        "info": "reference_check", **check,
        # Seconds since the process started: where a run's time went.
        "clock": {
            "setup_s": setup_s, "measured_at_s": measured_at,
            "check_from_s": checked_from,
            "check_to_s": time.perf_counter() - started,
        },
    }))
    finite = all(map(math.isfinite, (loss_first, loss_at_k, log.last_loss)))
    units = {m["name"]: m["unit"] for m in listed}
    return {
        "correct": bool(
            finite and log.failed == 0 and loss_at_k < loss_first
            and check["agrees"]
        ),
        "attempted": log.windows,
        "failed": log.failed,
        "metrics": {
            name: {"value": values[name], "unit": unit}
            for name, unit in units.items() if name in values
        },
        "device": device,
        **result,
    }
