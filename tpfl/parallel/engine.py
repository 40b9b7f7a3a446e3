"""Federation engine — an entire federation round as ONE sharded XLA
program over the TPU mesh, with a device-side multi-round loop.

This is the pod-scale seam the rest of tpfl rides (Podracer's Anakin
architecture: put the whole learner loop on device as one sharded
program; BlazeFL's bar: the fast path stays seed-deterministic):

- **Local train** — every node's local fit (epochs x scan over
  batches) is one ``vmap`` over the node axis, exactly the math of
  ``JaxLearner``/``VmapFederation`` (FedAvg, FedProx proximal pull,
  SCAFFOLD control variates).
- **Gossip as collective** — on a mesh the node axis is sharded over
  chips (``shard_map`` + ``PartitionSpec("nodes")``) and the gossip
  exchange + streaming FedAvg fold become per-device partial weighted
  sums reduced by ``lax.psum`` over the ``nodes`` axis: the all-reduce
  over ICI IS the intra-pod gossip. Without a mesh the fold is the
  masked weighted einsum — numerically the path
  ``VmapFederation.round`` always ran.
- **Multi-round windows** — ``run_rounds(..., n_rounds=K)`` folds K
  federation rounds into one ``lax.fori_loop`` inside the SAME
  program, so the ~67 ms host dispatch RTT is paid once per window
  instead of once per round (``Settings.SHARD_ROUNDS_PER_DISPATCH``).
- **Node padding** — node counts that do not divide the mesh are
  padded with zero-weight clone rows (``tpfl.parallel.mesh`` helpers);
  the masked-mean fold ignores w=0 entries exactly, so padding is
  numerics-free and every chip keeps an equal shard.
- **2D nodes x model meshes** — a ``model`` axis (explicit Mesh or
  ``Settings.SHARD_MODEL`` via ``mesh="auto"``) shards each node's
  parameters/optimizer state over chips per a
  :class:`~tpfl.parallel.mesh.SpecLayout` per-leaf PartitionSpec
  policy (transformer embeddings/QKV/FFN shard; MLP/CNN leaves ride
  replicated), so the largest federatable model is no longer one
  chip's HBM. The 2D program is the SAME un-wrapped round body under
  GSPMD: XLA partitions it from the layout shardings — the fold's
  node-axis reduction still lowers to an all-reduce over ``nodes``
  only (each model shard folds its own slice) and the layout's TP/FSDP
  collectives ride the ``model`` axis. Transformers additionally get
  ring-attention sequence parallelism over ``model``
  (``sequence_parallel=True``). 1D meshes keep the manual shard_map
  lowering byte-identical to the pre-2D engine.
- **Device-side wire codecs** — ``Settings.ENGINE_WIRE_CODEC`` lowers
  the PR-1 payload codecs INTO the round program: each node's trained
  params pass a per-leaf int8-quantize→dequantize (and/or top-k mask)
  round-trip before the gossip psum, so the exchange leg ships
  int8/sparse tensors over ICI/DCN natively and ``wire_bytes``
  becomes a device-side carry series. "dense" (default) lowers the
  byte-identical pre-codec program (separate cache slot).
- **In-program telemetry** — ``Settings.ENGINE_TELEMETRY`` threads a
  fixed-shape ``[n_rounds, ...]`` carry through the window (per round
  and per node: loss, update norm, reference cosine; per round:
  global-model delta norm, participation, weight mass — all from
  values the program already holds) and fans each window out into the
  observatory planes at close (``tpfl.management.engine_obs``).
  Disabled, the carry is ELIDED: the program lowers byte-identical to
  the pre-telemetry path (separate cache slot); enabled, model
  outputs stay byte-identical — telemetry is read-only.
- **FedBuff async rounds** — ``run_rounds(..., schedule=...)`` runs
  the ``fedbuff`` program variant: a seeded per-round arrival mask
  (:class:`FedBuffSchedule`, lowered from a
  ``TrainerSpeedPlan``-style speed skew) gates which nodes fold each
  round, arriving contributions are staleness-weighted
  ``w(τ) = 1/(1+τ)^ASYNC_STALENESS_EXP`` — exactly the gRPC
  aggregator's ``staleness_weight`` — and stragglers keep their local
  training instead of the fold broadcast, so a window no longer
  degrades to its slowest node. With telemetry on, the carry grows a
  per-node ``staleness`` row the observatory replays into the ledger
  and AsyncController exactly like gRPC-tier arrivals.
- **Free-running windows** — :meth:`FederationEngine.dispatch_window`
  returns an :class:`EngineWindow` handle instead of blocking: the
  outputs are JAX async futures (chainable into the next dispatch
  while the device still runs this one) and the telemetry carry's D2H
  copy starts non-blocking at dispatch, so ``finalize()`` — profiler
  attribution + observatory replay — is host work that overlaps the
  NEXT window (``tpfl.parallel.window_pipeline``, the Sebulba split).

Determinism discipline: at a FIXED device count, same seed => the same
byte-identical global model (all reductions have a fixed shape and
order); changing the device count regroups the fold's partial sums and
may shift last-ulp bits — see docs/scaling.md. The single-device
program is the exact ``VmapFederation`` round program, so the engine
is numerically equivalent to the legacy per-round path there.

Consumers: :class:`~tpfl.parallel.federation.VmapFederation` (all its
round programs are built here), the batched-fit pool
(:func:`build_batched_fit_program` / :func:`maybe_nodes_mesh`),
:class:`~tpfl.parallel.federation_learner.FederationLearner` (round
windows), and the chip benchmark (``benchmark/harness.py``).
"""

from __future__ import annotations

import re
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpfl import concurrency
from tpfl.learning import compression
from tpfl.learning.jax_learner import (
    TrainState,
    cross_entropy_loss,
    default_optimizer,
    make_train_step,
)
from tpfl.management import profiling, tracing
from tpfl.parallel import ranksafe
from tpfl.parallel.compat import shard_map
from tpfl.parallel.distributed import global_put, is_multiprocess
from tpfl.parallel.mesh import (
    HOST_AXIS,
    MODEL_AXIS,
    NODE_AXIS,
    SpecLayout,
    create_mesh,
    federation_sharding,
    global_model_shardings,
    layout_for_module,
    mesh_axis_size,
    node_shard_dims,
    node_shard_size,
    pad_node_axis,
    pad_node_weights,
    padded_node_count,
    replicated,
    stacked_model_shardings,
    valid_node_mask,
)
from tpfl.settings import Settings

_ALGORITHMS = ("fedavg", "fedprox", "scaffold")

#: The ENGINE_TELEMETRY carry schema (what the telemetry program
#: variant appends as its sixth output and
#: ``tpfl.management.engine_obs.replay_window`` consumes): per-round
#: PER-NODE ``[n_rounds, padded_nodes]`` buffers, then per-round
#: ``[n_rounds]`` scalars.
TELEMETRY_NODE_FIELDS = ("loss", "update_norm", "cos_ref")
TELEMETRY_ROUND_FIELDS = (
    "delta_norm", "model_norm", "participation", "weight_mass",
    "wire_bytes",
)
TELEMETRY_FIELDS = TELEMETRY_NODE_FIELDS + TELEMETRY_ROUND_FIELDS
#: Extra per-node carry row of the fedbuff variant: each arrival's
#: staleness ordinal τ (−1 on rounds the node does not arrive) —
#: what ``engine_obs.replay_window`` feeds the ledger's staleness
#: column and the AsyncController's arrival observations.
TELEMETRY_STALENESS_FIELD = "staleness"
#: Extra per-round carry row of cross-host (3D-mesh) programs: the
#: round's DCN payload bytes — the per-host partial aggregates that
#: cross the ``hosts`` axis, under the active ENGINE_WIRE_CODEC.
TELEMETRY_DCN_FIELD = "dcn_bytes"


# --- auto mesh resolution (Settings.SHARD_* knobs) -----------------------

# unguarded: process-wide memo of immutable Mesh objects keyed by
# (device count, model-axis size, hosts-axis size); worst case under a
# race is building the same Mesh twice.
_auto_meshes: dict[tuple[int, int, int], Mesh] = {}


def shard_device_count() -> int:
    """Devices the SHARD_* knobs allow the engine to spread over:
    0 (default) = all devices (GLOBAL — across every process of a
    jax.distributed world), else min(knob, available)."""
    n = len(jax.devices())
    cap = int(Settings.SHARD_DEVICES)
    return n if cap <= 0 else min(cap, n)


def resolve_shard_hosts() -> int:
    """The ``hosts`` axis size the ``SHARD_HOSTS`` knob selects:
    1 = off (the single-host layout), 0 = auto — one slot per
    participating process (``jax.process_count()``; 1 for a lone
    process, so auto is a no-op outside a jax.distributed world),
    H > 1 = forced (valid single-process too: the hosts axis then
    spans local devices, the CI parity harness's trick)."""
    h = int(Settings.SHARD_HOSTS)
    if h == 0:
        h = jax.process_count()
    return max(1, h)


def auto_mesh() -> Optional[Mesh]:
    """The mesh the ``SHARD_NODES`` knobs select: all allowed local
    devices on one ``nodes`` axis (``SHARD_MODEL`` = 1, the default —
    byte-identical programs to the pre-2D path), the 2D
    ``nodes x model`` mesh when ``SHARD_MODEL`` = M > 1 (``nodes`` =
    devices / M; M must divide), and/or the 3D ``hosts x nodes
    [x model]`` mesh when ``SHARD_HOSTS`` resolves above 1
    (:func:`resolve_shard_hosts`) — the hosts axis leads, so each
    process' devices form one contiguous hosts-row and cross-host
    collectives ride DCN. None when sharding is off or there is only
    one device."""
    if not Settings.SHARD_NODES:
        return None
    d = shard_device_count()
    if d <= 1:
        return None
    m = max(1, int(Settings.SHARD_MODEL))
    h = resolve_shard_hosts()
    if d % (m * h) != 0:
        raise ValueError(
            f"SHARD_MODEL={m} x SHARD_HOSTS={h} does not divide the "
            f"{d} allowed devices"
        )
    mesh = _auto_meshes.get((d, m, h))
    if mesh is None:
        axes = {}
        if h > 1:
            axes[HOST_AXIS] = h
        axes[NODE_AXIS] = d // (m * h)
        if m > 1:
            axes[MODEL_AXIS] = m
        mesh = _auto_meshes[(d, m, h)] = create_mesh(
            axes, devices=jax.devices()[:d]
        )
    return mesh


def maybe_nodes_mesh(width: int) -> Optional[Mesh]:
    """Mesh for sharding a batched node axis of ``width`` rows (the
    batched-fit pool's chunk), or None when sharding is off, there is
    one device, or ``width`` does not divide — the pool's power-of-two
    bucketing makes divisibility the common case on 2^k-chip hosts.
    On a 3D mesh the node axis shards over ``hosts x nodes`` combined,
    so that product is the divisor."""
    mesh = auto_mesh()
    if mesh is None or width % node_shard_size(mesh) != 0:
        return None
    return mesh


def sample_participants(
    population: int, k: int, seed: int, round: int
) -> np.ndarray:
    """Deterministic per-round participant sample: ``k`` distinct
    client indices out of ``population`` registered clients, seeded by
    ``(seed, round)`` — the cross-device sampling discipline for
    population scales where only the ACTIVE participants' state may
    exist on host/device (sim100k: population state O(active), not
    O(population))."""
    if k > population:
        raise ValueError(f"cannot sample {k} of {population} clients")
    rng = np.random.default_rng(np.random.SeedSequence([seed, round]))
    return np.sort(rng.choice(population, size=k, replace=False))


class FedBuffSchedule:
    """A per-round arrival/staleness schedule for the engine's
    ``fedbuff`` program variant — the host-side lowering of a speed
    plan to device-side masks.

    ``arrivals`` ``[n_rounds, n_nodes]`` is the 0/1 arrival mask: a 1
    at ``(r, i)`` means node ``i``'s buffered contribution reaches the
    aggregator at round ``r`` (it folds, staleness-weighted, and
    receives the broadcast); a 0 means the node is still in flight —
    it keeps training locally and its accumulated update arrives at a
    later round. ``taus`` ``[n_rounds, n_nodes]`` carries each
    arrival's staleness ordinal τ (version distance since the node
    last pulled the global model — the gRPC aggregator's definition),
    zero on non-arrival rounds.

    Built from a :class:`~tpfl.communication.faults.TrainerSpeedPlan`
    (:meth:`from_plan`) the schedule is fully seeded: same plan, same
    window → the same masks, byte for byte — the engine's determinism
    discipline extends over async participation. Every round must have
    at least one arrival (an all-zero round would silently re-enter
    the fold's uniform fallback with semantics no async tier has).
    """

    def __init__(self, arrivals: Any, taus: Any) -> None:
        # host-sync: schedule construction is pure host numpy — the
        # masks exist host-side before any dispatch touches them.
        arrivals = np.asarray(arrivals, np.float32)
        taus = np.asarray(taus, np.float32)  # host-sync: host numpy
        if arrivals.ndim != 2 or arrivals.shape != taus.shape:
            raise ValueError(
                f"arrivals/taus must be matching [n_rounds, n_nodes] "
                f"arrays, got {arrivals.shape} vs {taus.shape}"
            )
        if not (arrivals.sum(axis=1) > 0).all():
            empty = int(np.flatnonzero(arrivals.sum(axis=1) == 0)[0])
            raise ValueError(
                f"round {empty} of the schedule has no arrivals — every "
                f"fedbuff round needs at least one folding node"
            )
        self.arrivals = arrivals
        self.taus = taus
        self.n_rounds, self.n_nodes = (
            int(arrivals.shape[0]), int(arrivals.shape[1])
        )

    @classmethod
    def from_periods(
        cls, periods: Any, n_rounds: int, start_round: int = 0
    ) -> "FedBuffSchedule":
        """Periodic arrivals from per-node periods in ticks (node
        ``i`` arrives every ``periods[i]`` rounds, first at global
        round ``periods[i] - 1``): a period-``p`` node's contribution
        always carries ``τ = p - 1`` — it trained from the model
        version of its previous pull, ``p`` folds ago. ``start_round``
        keys multi-window continuation (pass the engine's cumulative
        round ordinal so chained windows continue one global
        schedule)."""
        periods = np.asarray(periods, np.int64)  # host-sync: host numpy
        if periods.ndim != 1 or (periods < 1).any():
            raise ValueError(f"periods must be [n] ints >= 1: {periods}")
        g = start_round + np.arange(int(n_rounds), dtype=np.int64)[:, None]
        arrive = ((g + 1) % periods[None, :]) == 0
        taus = np.where(arrive, periods[None, :] - 1, 0)
        return cls(arrive.astype(np.float32), taus.astype(np.float32))

    @classmethod
    def from_plan(
        cls,
        plan: Any,
        addrs: "Sequence[str]",
        n_rounds: int,
        start_round: int = 0,
        tick: "float | None" = None,
    ) -> "FedBuffSchedule":
        """Lower a ``TrainerSpeedPlan`` to device masks: each node's
        delay is quantized to round ticks (``tick`` defaults to the
        fastest node's positive delay, so the fastest nodes arrive
        every round) and the per-node periods drive
        :meth:`from_periods`. Deterministic: the plan's seeded delays
        are the only randomness."""
        delays = np.asarray(
            [max(float(plan.delay_for(a)), 0.0) for a in addrs], np.float64
        )
        if tick is None:
            positive = delays[delays > 0]
            tick = float(positive.min()) if positive.size else 1.0
        periods = np.maximum(
            1, np.round(delays / max(float(tick), 1e-12)).astype(np.int64)
        )
        return cls.from_periods(periods, int(n_rounds), int(start_round))

    def window(self, start: int, n_rounds: int) -> "FedBuffSchedule":
        """The ``[start, start + n_rounds)`` slice as its own schedule
        — how the :class:`~tpfl.parallel.window_pipeline.WindowPipeline`
        carves one full-run schedule into per-dispatch windows (row
        slicing preserves the every-round-arrives invariant)."""
        if start < 0 or start + n_rounds > self.n_rounds:
            raise ValueError(
                f"window [{start}, {start + n_rounds}) outside the "
                f"schedule's {self.n_rounds} rounds"
            )
        return FedBuffSchedule(
            self.arrivals[start:start + n_rounds],
            self.taus[start:start + n_rounds],
        )


def start_host_copy(tree: Any) -> None:
    """Begin a NON-BLOCKING device→host copy of every array leaf, so a
    later ``np.asarray`` over the tree reads host memory instead of
    stalling the dispatch pipeline — the telemetry carry's fetch
    starts here at dispatch and completes while the next window runs
    (satellite of the Sebulba split; see docs/scaling.md)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:
                # Backends without async D2H degrade to the blocking
                # np.asarray at finalize — correctness is unchanged.
                pass


class EngineWindow:
    """One dispatched engine window in flight — the free-running seam.

    JAX dispatch is asynchronous: the program call returns immediately
    with futures for every output while the device works. This handle
    splits :meth:`FederationEngine.run_rounds` at exactly that line:
    :meth:`FederationEngine.dispatch_window` returns the handle with
    the output futures (chainable straight into the next dispatch —
    double-buffered donation: window N+1 consumes window N's output
    buffers, which is the only copy of the state either way), and
    :meth:`finalize` performs the window's HOST work — round-profiler
    attribution and the telemetry fan-out
    (``engine_obs.replay_window``) — which the
    :class:`~tpfl.parallel.window_pipeline.WindowPipeline` runs while
    the device executes the NEXT window. The telemetry carry's D2H
    copy was started non-blocking at dispatch (:func:`start_host_copy`),
    so by finalize time ``np.asarray`` reads host memory.

    ``run_rounds`` is ``dispatch_window(...).finalize()`` — the
    sequential path is the pipeline's degenerate depth-0 case, byte-
    and side-effect-identical to the pre-pipeline engine."""

    __slots__ = (
        "_engine", "_kind", "_has_aux", "_outs", "_tele", "_w",
        "_n_rounds", "_window_start", "_ordinal", "_prof", "_node_tag",
        "_t0", "_t1", "_finalized", "_result",
    )

    def __init__(
        self, engine: "FederationEngine", kind: str, has_aux: bool,
        outs: tuple, tele: Optional[dict], w: Any, n_rounds: int,
        window_start: int, ordinal: int, prof: bool, node_tag: str,
        t0: float, t1: float,
    ) -> None:
        self._engine = engine
        self._kind = kind
        self._has_aux = has_aux
        self._outs = outs
        self._tele = tele
        self._w = w
        self._n_rounds = int(n_rounds)
        self._window_start = int(window_start)
        self._ordinal = int(ordinal)
        self._prof = bool(prof)
        self._node_tag = node_tag
        self._t0 = t0
        self._t1 = t1
        self._finalized = False
        self._result: Optional[tuple] = None

    # --- chaining (pre-finalize): the raw output futures -----------------

    @property
    def params(self) -> Any:
        """Stacked output params (async futures — safe to chain into
        the next dispatch immediately)."""
        return self._outs[0]

    @property
    def aux(self) -> Any:
        return self._outs[3]

    @property
    def scaffold_state(self) -> tuple[Any, Any]:
        return self._outs[1], self._outs[2]

    @property
    def losses(self) -> Any:
        """Last round's per-node losses (padded length, futures)."""
        return self._outs[4]

    @property
    def n_rounds(self) -> int:
        return self._n_rounds

    def wait(self) -> None:
        """Block until the window's device work completes — the
        pipeline's ready-timestamp probe for the device-idle-gap
        accounting (and nothing else: finalize does the host work)."""
        with tracing.engine_span("wait", self._window_start):
            # host-sync: deliberate ready-probe — the pipeline calls this
            # AFTER dispatching the next window, so the block measures
            # device completion, never stalls the dispatch queue.
            jax.block_until_ready(self._outs[4])

    # --- the window's host work ------------------------------------------

    def finalize(self) -> tuple:
        """Profiler attribution + telemetry fan-out, then the caller-
        facing result tuple (``run_rounds``' return conventions).
        Idempotent: the host work runs once; later calls return the
        cached tuple."""
        if self._finalized:
            return self._result
        with tracing.engine_span("finalize", self._window_start):
            out_params, out_c, out_cg, out_aux, losses = self._outs
            if self._prof:
                jax.block_until_ready(losses)
                t2 = time.monotonic()
                # The dispatch gap is paid ONCE for the whole window — the
                # engine's core claim, visible in tpfl_round_attr_seconds.
                # The window ordinal targets THIS window's open profiler
                # record: under the pipeline, window N+1's record opened
                # (at dispatch) before window N's closes here.
                profiling.rounds.add(self._node_tag, "dispatch",
                                     self._t1 - self._t0, round=self._ordinal)
                profiling.rounds.add(self._node_tag, "train", t2 - self._t1,
                                     round=self._ordinal)
                profiling.rounds.end_round(self._node_tag, self._ordinal)
            tele = self._tele
            if tele is not None and any(
                hasattr(v, "is_fully_addressable") and not v.is_fully_addressable
                for v in tele.values()
            ):
                # Multi-process window: the per-node telemetry rows are
                # sharded across processes, so no process holds the full
                # window — the observatory fan-out is a single-host plane
                # (documented in docs/scaling.md); run cross-host windows
                # with ENGINE_TELEMETRY off, or read the per-process
                # registry series instead.
                tele = None
            if tele is not None:
                # One host sync per WINDOW — and when the non-blocking D2H
                # copy (started at dispatch) has landed, not even that:
                # np.asarray reads the host-resident buffer.
                from tpfl.management import engine_obs

                eng = self._engine
                with tracing.engine_span("tele_fetch", self._window_start):
                    host_tele = {k: np.asarray(v) for k, v in tele.items()}
                with tracing.engine_span("replay_window", self._window_start):
                    engine_obs.replay_window(
                        self._node_tag,
                        profiling.module_tag(eng.module),
                        self._window_start,
                        host_tele,
                        eng.n_nodes,
                        weights=np.asarray(self._w),
                        wall_seconds=time.monotonic() - self._t0,
                        dispatch_seconds=self._t1 - self._t0,
                        controller=eng.controller,
                    )
            if self._kind == "scaffold":
                result: tuple = (out_params, out_aux, (out_c, out_cg), losses)
            elif self._has_aux:
                result = (out_params, out_aux, losses)
            else:
                result = (out_params, losses)
            self._finalized = True
            self._result = result
            return result

    def abandon(self) -> None:
        """Drop an in-flight window WITHOUT its host leg (no telemetry
        fan-out, no profiler rows): block until the device program has
        retired — the handle holds the only reference to the donated
        state's successor buffers, so dropping it while the program
        still runs would free device memory out from under the
        executing dispatch — then mark the handle finalized with no
        result. The shutdown seam for ``Node.stop`` and the chaos
        harness's crash paths (``window_pipeline.interrupt_for``):
        a stopping node's open window is retired cleanly instead of
        leaking into the runtime. Idempotent, and a no-op after
        :meth:`finalize`."""
        if self._finalized:
            return
        self._finalized = True
        try:
            # host-sync: shutdown boundary — deliberate drain so the
            # donated buffers outlive the executing program.
            jax.block_until_ready(self._outs[4])
        except Exception:
            pass  # a failed dispatch already dumped its flight ring
        self._result = None


def _sequence_parallel_module(module: Any, mesh: Mesh) -> Any:
    """Clone a transformer module onto ring attention over the 2D
    mesh's ``model`` axis: each model shard holds one sequence block,
    K/V rotate the ring (``tpfl.parallel.ring_attention``) — sequence
    parallelism composed with the layout's FSDP/TP parameter sharding.
    Modules without an unset ``attention_fn`` seam (MLP/CNN/ResNet, or
    a transformer the caller already pinned an attention onto) pass
    through untouched. Sequence lengths that do not divide the model
    axis fall back to the single-device blockwise path at trace time
    (static shapes — a Python branch, not a lowered one)."""
    if getattr(module, "attention_fn", False) is not None:
        return module
    from functools import partial

    from tpfl.parallel.ring_attention import (
        blockwise_attention,
        ring_attention,
    )

    msize = mesh_axis_size(mesh, MODEL_AXIS)
    spec = PartitionSpec(None, MODEL_AXIS, None, None)

    def model_ring_attention(q, k, v, causal: bool = True):
        if q.shape[1] % msize != 0:
            return blockwise_attention(q, k, v, causal=causal)
        fn = shard_map(
            partial(ring_attention, axis_name=MODEL_AXIS, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)

    return module.clone(attention_fn=model_ring_attention)


def _round_node_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for per-round per-node ``[n_rounds, nodes]`` arrays
    (weights / attack scales / fedbuff masks): rounds replicated, the
    node axis over the mesh's node-shard dims (``hosts x nodes`` on a
    3D mesh — the same placement as the stacked state)."""
    dims = node_shard_dims(mesh)
    return NamedSharding(
        mesh, PartitionSpec(None, dims if len(dims) > 1 else dims[0])
    )


# --- the engine ----------------------------------------------------------


class FederationEngine:
    """N-node federated training compiled to one (optionally sharded)
    XLA round program with device-side multi-round windows.

    Args mirror :class:`~tpfl.parallel.federation.VmapFederation` (it
    delegates here): ``mesh`` may be a Mesh with a ``nodes`` axis,
    None (single device), or ``"auto"`` (resolve from the
    ``SHARD_NODES``/``SHARD_DEVICES`` knobs at construction).

    Node-stacked state is padded to ``padded_nodes`` (a NODE-axis
    multiple) with zero-weight clone rows; ``unpad`` strips them on
    host. Losses and stacked outputs ride padded.

    2D meshes (a ``model`` axis alongside ``nodes`` — built explicitly
    or resolved from ``SHARD_MODEL`` via ``mesh="auto"``) additionally
    shard each node's parameters/optimizer state over ``model`` per the
    ``layout`` per-leaf PartitionSpec policy
    (:class:`~tpfl.parallel.mesh.SpecLayout`; None = resolve from
    ``Settings.SHARD_LAYOUT`` / the module's declared layout): local
    train runs FSDP/TP-sharded per node while the fold still reduces
    over ``nodes`` only — each model shard folds its own slice. On a
    1D mesh the engine's programs are the exact pre-2D lowering.

    ``sequence_parallel`` (2D meshes, default True): a transformer
    module whose ``attention_fn`` is unset attends via the in-tree
    ring attention over the ``model`` axis — each model shard holds
    one sequence block, K/V rotate the ring — whenever the sequence
    length divides the axis (else the single-device blockwise path)."""

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
        layout: "SpecLayout | str | None" = None,
        sequence_parallel: bool = True,
    ) -> None:
        # The set-up account opens with the engine, on the constructor's
        # first line (see _account_init).
        profiling.observatory.open_setup_account()
        t_init = time.monotonic()
        if aux_mode not in ("mean", "local"):
            raise ValueError(f"aux_mode must be 'mean' or 'local', got {aux_mode!r}")
        if algorithm not in _ALGORITHMS:
            raise ValueError(
                f"algorithm must be one of {_ALGORITHMS}, got {algorithm!r}"
            )
        self.module = module
        self.n_nodes = int(n_nodes)
        self.mesh = auto_mesh() if mesh == "auto" else mesh
        #: Model-parallel axis size (1 on 1D meshes / no mesh).
        self.model_axes = mesh_axis_size(self.mesh, MODEL_AXIS)
        if isinstance(layout, SpecLayout):
            self.layout = layout
        else:
            self.layout = layout_for_module(
                module, layout or str(Settings.SHARD_LAYOUT)
            )
        if self.model_axes > 1 and sequence_parallel:
            self.module = module = _sequence_parallel_module(
                module, self.mesh
            )
        self.learning_rate = float(learning_rate)
        self._opt = (optimizer_factory or default_optimizer)(learning_rate)
        self._loss_fn = loss_fn
        #: The module's head computes the mean training loss itself
        #: (``owns_cross_entropy``: ``apply(..., targets=y)``) and the
        #: engine's loss is the canonical one it owns; local training
        #: then never sees logits. Static per engine, and recorded in
        #: the window programs' observatory names (``:hl``).
        self.head_owns_loss = loss_fn is cross_entropy_loss and bool(
            getattr(module, "owns_cross_entropy", False)
        )
        self.seed = seed
        self.aux_mode = aux_mode
        self.algorithm = algorithm
        self.prox_mu = float(prox_mu)
        #: Stacked leading dimension: n_nodes rounded up to a device
        #: multiple (== n_nodes without a mesh).
        # ephemeral: derived — resize_nodes/import_state re-derive it
        # from the checkpointed n_nodes on this mesh.
        self.padded_nodes = padded_node_count(self.n_nodes, self.mesh)
        # unguarded: single-owner — an engine is built and driven by one
        # thread (a learner's fit thread or a driver script); the caches below
        # are only touched from that thread.
        # ephemeral: compiled-program cache — rebuilt per mesh/process
        # (the persistent XLA cache makes rebuilds warm, not a resume
        # concern).
        self._programs: dict[tuple, Callable] = {}
        # unguarded: single-owner (see _programs)
        # ephemeral: observatory/contract wrappers over _programs.
        self._wrapped: dict[tuple, Callable] = {}
        # unguarded: single-owner (see _programs)
        # ephemeral: per-dispatch scratch (see _wrapped_program).
        self._fresh_program: Optional[str] = None
        # unguarded: single-owner (see _programs)
        # ephemeral: compiled-program cache (see _programs).
        self._eval_fns: dict[bool, Callable] = {}
        # unguarded: single-owner (see _programs) — per-cache-key
        # lowered-HLO fingerprints for the RANK_CONTRACTS dispatch
        # receipts (tpfl.parallel.ranksafe); computed lazily once per
        # key, only when the knob is on.
        # ephemeral: derived from _programs (see _programs).
        self._hlo_digests: dict[tuple, str] = {}
        # unguarded: single-owner (see _programs) — the per-arg
        # sharding pytrees of the most recent _prepare_args placement;
        # the 2D program builder lowers with them so buffer donation
        # aliases instead of freeing (see _model_mesh_shardings).
        # ephemeral: per-dispatch scratch — recomputed by every
        # _prepare_args call, meaningless across a resume.
        self._arg_shardings: Optional[tuple] = None
        # unguarded: single-owner (see _programs) — dispatch-window
        # ordinal for round-profiler attribution labels.
        self._windows = 0
        # unguarded: single-owner (see _programs) — cumulative rounds
        # run through run_rounds: the engine-plane fan-out's round
        # ordinals stay monotonic across windows.
        self._rounds_done = 0
        #: Optional AsyncController fed by the telemetry fan-out's
        #: staleness rows (``engine_obs.replay_window``): set it to a
        #: node's ``state.async_controller`` and fedbuff windows drive
        #: the same concurrency-adaptation observations as gRPC-tier
        #: arrivals. None (default) = no feed.
        self.controller: Optional[Any] = None
        #: Optional MembershipView (tpfl.parallel.membership) driving
        #: the elastic weight mask; attach_membership keeps this
        #: engine's node axis at the view's capacity tier. None
        #: (default) = fixed membership.
        self.membership: Optional[Any] = None
        #: Optional ClientPopulation (tpfl.parallel.population): the
        #: cross-device tier — this engine's resident nodes become
        #: edge aggregators and each round's cohort is sampled from
        #: the registered census (attach_population). None (default)
        #: = every logical node is resident.
        self.population: Optional[Any] = None
        #: [padded_nodes] 1/0 mask of real vs pad rows (the uniform
        #: fallback denominator when a round's weights are all-zero).
        # ephemeral: derived — resize_nodes/import_state re-derive it
        # from the checkpointed n_nodes (see padded_nodes).
        self.valid = valid_node_mask(self.n_nodes, self.padded_nodes)
        self._account_init(t_init)

    # --- state / data placement ---

    def _shard(self, tree: Any) -> Any:
        """Node-axis placement for node-stacked DATA (model-axis
        replicated — every model shard sees the node's full batch).
        ``global_put`` == ``jax.device_put`` single-process; in a
        multi-process world each process contributes its addressable
        shards of the global array."""
        if self.mesh is None:
            return tree
        return global_put(tree, federation_sharding(self.mesh))

    def _shard_state(self, tree: Any) -> Any:
        """Per-leaf placement for node-stacked MODEL STATE (params /
        variates / aux): the node axis over ``nodes`` (``hosts x
        nodes`` on 3D meshes) and, on a 2D mesh, each leaf's model
        dims over ``model`` per the layout."""
        if self.mesh is None:
            return tree
        if self.model_axes > 1:
            return global_put(
                tree, stacked_model_shardings(self.mesh, tree, self.layout)
            )
        return global_put(tree, federation_sharding(self.mesh))

    def _shard_global(self, tree: Any) -> Any:
        """Placement for UNSTACKED node-replicated state (SCAFFOLD's
        ``c_global``): replicated over ``nodes``, layout-sharded over
        ``model`` on a 2D mesh."""
        if self.mesh is None:
            return tree
        if self.model_axes > 1:
            return global_put(
                tree, global_model_shardings(self.mesh, tree, self.layout)
            )
        return global_put(tree, replicated(self.mesh))

    def init_state(self, input_shape: tuple[int, ...]) -> tuple[Any, Any]:
        """(stacked params, stacked aux) on the padded node axis — aux
        is ``{}`` for modules without mutable collections. Token
        modules declaring ``input_dtype`` (TransformerLM: int32 ids)
        initialize from it, like ``create_model``."""
        dummy = jnp.zeros(
            (1, *input_shape),
            getattr(self.module, "input_dtype", jnp.float32),
        )
        variables = self.module.init(
            jax.random.PRNGKey(self.seed), dummy, train=False
        )
        params = variables["params"]
        aux = {k: v for k, v in variables.items() if k != "params"}
        return (
            self._shard_state(self.broadcast_params(params)),
            self._shard_state(self.broadcast_params(aux)),
        )

    def init_params(self, input_shape: tuple[int, ...]) -> Any:
        """Stacked [padded_nodes, ...] params (aux-free modules)."""
        params, aux = self.init_state(input_shape)
        if aux:
            raise ValueError(
                f"Module has mutable collections {sorted(aux)} — use "
                f"init_state() and pass aux to round()/evaluate()."
            )
        return params

    def init_scaffold_state(self, params: Any) -> tuple[Any, Any]:
        """(c_locals [padded, ...], c_global [...]) zero control
        variates; c_global node-replicated (model-axis sharded per the
        layout on 2D meshes, like every other model-shaped tree)."""
        c_locals = jax.tree_util.tree_map(jnp.zeros_like, params)
        c_global = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape[1:], p.dtype), params
        )
        return self._shard_state(c_locals), self._shard_global(c_global)

    def broadcast_params(self, tree: Any) -> Any:
        """One model's tree broadcast onto the padded node axis — the
        cross-device pattern: the global model is the ONLY persistent
        state; stacking K active participants from it each round keeps
        memory O(active), not O(population)."""
        n = self.padded_nodes
        return jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(jnp.asarray(p)[None], (n, *jnp.shape(p))),
            tree,
        )

    def pad_stacked(self, tree: Any) -> Any:
        """Pad a node-stacked tree's leading axis to ``padded_nodes``
        (clone rows; exact no-op when already padded)."""
        return pad_node_axis(tree, self.padded_nodes)

    def pad_weights(self, weights: Optional[Any]) -> Any:
        """[n] (or per-round [R, n]) weights -> padded f32 with zero
        pad entries; None -> uniform full participation."""
        if weights is None:
            weights = jnp.ones((self.n_nodes,), jnp.float32)
        return pad_node_weights(weights, self.padded_nodes)

    def unpad(self, tree: Any) -> Any:
        """Strip pad rows from a node-stacked output (host-side)."""
        if self.padded_nodes == self.n_nodes:
            return tree
        return jax.tree_util.tree_map(lambda x: x[: self.n_nodes], tree)

    def pad_attack_scales(self, scales: Any) -> Any:
        """[n] (or per-round [R, n]) per-node attack multipliers ->
        padded f32 with ONE-valued pad entries (a pad row's params must
        ride untouched: its fold weight is already zero)."""
        s = jnp.asarray(scales, jnp.float32)
        if s.shape[-1] != self.n_nodes:
            raise ValueError(
                f"attack_scales last axis is {s.shape[-1]} for "
                f"{self.n_nodes} nodes"
            )
        extra = self.padded_nodes - self.n_nodes
        if extra == 0:
            return s
        pad_shape = s.shape[:-1] + (extra,)
        return jnp.concatenate(
            [s, jnp.ones(pad_shape, jnp.float32)], axis=-1
        )

    def shard_data(self, xs: Any, ys: Any) -> tuple[Any, Any]:
        """Pad + place node-stacked batch arrays [n, n_batches, b, ...]
        on the mesh (node axis sharded)."""
        return (
            self._shard(self.pad_stacked(jnp.asarray(xs))),
            self._shard(self.pad_stacked(jnp.asarray(ys))),
        )

    # --- elastic membership ----------------------------------------------

    def resize_nodes(self, n_nodes: int) -> None:
        """Move this engine to a new capacity tier: re-derive the
        padded node axis and validity mask. Cached programs are KEPT —
        the capacity is a program-cache key axis, so each tier's
        programs live in their own slots and returning to a
        previously-compiled tier is a cache hit (zero recompiles);
        only a never-seen tier lowers fresh."""
        self.n_nodes = int(n_nodes)
        self.padded_nodes = padded_node_count(self.n_nodes, self.mesh)
        self.valid = valid_node_mask(self.n_nodes, self.padded_nodes)

    def attach_membership(self, view: Any) -> None:
        """Drive this engine's node axis from a
        :class:`~tpfl.parallel.membership.MembershipView`: the engine
        follows the view's capacity tier (resizing now and on
        :meth:`sync_membership`), and callers take each window's fold
        weights from ``view.weights()`` — joins, leaves, crashes and
        quarantine verdicts become pure mask edits."""
        self.membership = view
        # Fleet plane: weakly registered so NodeMonitor's fleet sample
        # can gauge tier occupancy without touching the engine.
        from tpfl.management import fleetobs

        fleetobs.register_view(view)
        if int(view.capacity) != self.n_nodes:
            self.resize_nodes(int(view.capacity))

    def attach_population(self, population: Any) -> None:
        """Drive this engine from a
        :class:`~tpfl.parallel.population.ClientPopulation`: the
        engine's resident nodes become the cross-device tier's edge
        aggregators, each window's cohort comes from the population's
        seeded per-round sample (``population.begin_round``), and the
        registered census becomes a program-cache / contract axis of
        the round programs (``pop_size``) — attaching or resizing a
        population selects fresh cache slots, never mutates a
        compiled program. The sampled cohort must fit the engine's
        node axis: ``population.sample`` (+ edge residents) rows are
        stacked via :meth:`broadcast_params`, so live state stays
        O(sampled) regardless of the census."""
        self.population = population
        if population is not None:
            population.bind(self)
            from tpfl.management import fleetobs

            fleetobs.register_population(population)

    def sync_membership(self) -> bool:
        """Re-align the node axis with the attached view's tier (after
        its ``join``-driven promotions or ``maybe_resize`` demotions,
        the latter consulted against ``self.controller``). Returns
        whether the tier moved — i.e. whether the next window compiles
        a new-tier program instead of mask-editing the current one."""
        view = self.membership
        if view is None:
            return False
        view.maybe_resize(self.controller)
        if int(view.capacity) == self.n_nodes:
            return False
        self.resize_nodes(int(view.capacity))
        return True

    # --- checkpoint state -------------------------------------------------

    def export_state(
        self,
        params: Any,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
        quarantine: Optional[Any] = None,
    ) -> dict:
        """One checkpointable snapshot of the engine-side federation
        state: UNPADDED host-numpy logical rows (mesh-agnostic — a
        checkpoint written on a 1×1 mesh restores onto 4×2 and back,
        placement happens at :meth:`import_state`), plus the schedule
        position (``rounds_done`` — a resumed :class:`FedBuffSchedule`
        and the learner's seeded per-window data stream both index off
        it), the window ordinal, the seed the per-window RNG streams
        derive from, and the attached controller / membership (and an
        optional quarantine engine's) exported state.

        host-sync by design: checkpointing is a consumption boundary —
        callers snapshot OFF the critical path (the window pipeline
        rides the ``copy_to_host_async`` host leg)."""

        n = self.n_nodes

        def fetch(x: Any) -> np.ndarray:
            # host-sync: checkpoint consumption boundary (see above).
            # np.array, not np.asarray: on the CPU backend asarray is
            # a ZERO-COPY view of the device buffer, and a later
            # donating round may overwrite that buffer in place
            # (deserialized persistent-cache executables do) — the
            # checkpoint must own its bytes. Cross-host arrays are
            # replicated first through an identity dispatch (one
            # all-gather over DCN), so every process owns the full
            # logical rows and checkpoints stay mesh-agnostic.
            if (
                hasattr(x, "is_fully_addressable")
                and not x.is_fully_addressable
            ):
                x = jax.jit(
                    lambda a: a, out_shardings=replicated(self.mesh)
                )(x)
                x = x.addressable_data(0)
            # host-sync: checkpoint consumption boundary — export_state
            # runs between windows, never inside the dispatch loop.
            return np.array(x)

        def host(tree: Any) -> Any:
            return jax.tree_util.tree_map(
                lambda x: fetch(x)[:n], tree
            )

        state: dict = {
            "params": host(params),
            "n_nodes": int(self.n_nodes),
            "rounds_done": int(self._rounds_done),
            "windows": int(self._windows),
            "seed": int(self.seed),
        }
        if aux is not None:
            state["aux"] = host(aux)
        if scaffold_state is not None:
            c_locals, c_global = scaffold_state
            state["c_locals"] = host(c_locals)
            # host-sync: checkpoint consumption boundary (owning copy
            # — see fetch(); unstacked, so no row slice).
            state["c_global"] = jax.tree_util.tree_map(fetch, c_global)
        if self.controller is not None:
            state["controller"] = self.controller.state_export()
        if self.membership is not None:
            state["membership"] = self.membership.state_export()
        if self.population is not None:
            # O(active): only touched clients' records ride the
            # snapshot (tpfl.parallel.population), never the census.
            state["population"] = self.population.state_export()
        if quarantine is not None:
            state["quarantine"] = quarantine.state_export()
        return state

    def import_state(self, state: dict, quarantine: Optional[Any] = None) -> dict:
        """Restore an :meth:`export_state` snapshot onto THIS engine's
        mesh — the elastic half of kill-and-resume: the node axis
        resizes to the checkpoint's logical count, the host trees are
        re-padded and re-placed for this mesh's shape/layout
        (``_shard_state``), and the schedule position, controller,
        membership and (optionally) quarantine state come back live.
        Returns ``{"params", "aux", "scaffold_state"}`` ready for the
        next :meth:`dispatch_window` (absent pieces are None)."""
        n = int(state["n_nodes"])
        if n != self.n_nodes:
            self.resize_nodes(n)
        self._rounds_done = int(state.get("rounds_done", 0))
        self._windows = int(state.get("windows", 0))
        # The checkpointed seed wins over this engine's construction
        # seed: the per-window RNG streams (and the population's seeded
        # cohorts via the engine plumb) must continue the killed run's
        # sequence — resuming onto a differently-seeded engine used to
        # silently fork the stream (the state pass's export-only-key
        # finding; see tools/tpflcheck/state.py).
        self.seed = int(state.get("seed", self.seed))

        def place(tree: Any) -> Any:
            return self._shard_state(self.pad_stacked(tree))

        out: dict = {
            "params": place(state["params"]),
            "aux": None,
            "scaffold_state": None,
        }
        if "aux" in state:
            out["aux"] = place(state["aux"])
        if "c_locals" in state:
            out["scaffold_state"] = (
                place(state["c_locals"]),
                self._shard_global(state["c_global"]),
            )
        if self.controller is not None and state.get("controller"):
            self.controller.state_import(state["controller"])
        if state.get("membership"):
            if self.membership is None:
                from tpfl.parallel.membership import MembershipView

                self.membership = MembershipView.from_state(
                    state["membership"]
                )
            else:
                self.membership.state_import(state["membership"])
        if state.get("population"):
            if self.population is None:
                from tpfl.parallel.population import ClientPopulation

                self.population = ClientPopulation.from_state(
                    state["population"]
                )
                self.population.bind(self)
            else:
                self.population.state_import(state["population"])
        if quarantine is not None and state.get("quarantine"):
            quarantine.state_import(state["quarantine"])
        return out

    # --- program construction -------------------------------------------

    def _kind(self, aux: Optional[Any]) -> str:
        if self.algorithm == "scaffold":
            return "scaffold"
        return "aux" if aux is not None else "plain"

    def _make_prox(self) -> Callable[[Any, Any], Any]:
        """FedProx proximal term ``mu/2·||p - p0||²`` (constant 0.0
        for other algorithms keeps the round program free of the dead
        subtraction tree)."""
        if self.algorithm != "fedprox":
            return lambda p, p0: 0.0
        mu = self.prox_mu

        def prox(p, p0):
            sq = sum(
                jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)
                for a, b in zip(
                    jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(p0)
                )
            )
            return 0.5 * mu * sq

        return prox

    def _build_local_train(self, kind: str) -> Callable:
        """One node's local fit — the exact per-kind math of the legacy
        ``VmapFederation`` builders, unified behind a
        ``(params, c_i, c_g, aux, xb, yb) -> (params, c_i, aux, loss)``
        signature (``c_i``/``c_g``/``aux`` are empty pytrees for kinds
        that do not thread them, which XLA erases)."""
        opt, loss_fn, module = self._opt, self._loss_fn, self.module
        prox = self._make_prox()
        lr = self.learning_rate
        head_owns_loss = self.head_owns_loss

        def batch_loss(variables, x, y, train, mutable=False):
            """(mean loss of one batch, the collections it mutated or
            None): the ONE place every kind turns a batch into its
            loss. A module whose head owns the canonical loss computes
            it itself from ``targets``; any other returns logits."""
            targets = {"targets": y} if head_owns_loss else {}
            out = module.apply(
                variables, x, train=train, mutable=mutable, **targets
            )
            out, mutated = (out, None) if mutable is False else out
            return (out if head_owns_loss else loss_fn(out, y).mean()), mutated

        def local_train(params, c_i, c_g, aux, xb, yb, epochs):
            p0 = params  # round-start weights (FedProx anchor)
            if kind == "scaffold":
                # Fixed during the round (computed once, like the
                # protocol path's ScaffoldCallback).
                corr = jax.tree_util.tree_map(
                    lambda c, ci: (c - ci).astype(c.dtype), c_g, c_i
                )
            opt_state = opt.init(params)

            def batch_step(carry, batch):
                p, o, a = carry
                x, y = batch

                if kind == "plain":

                    def loss_of(pp):
                        loss, _ = batch_loss({"params": pp}, x, y, False)
                        return loss + prox(pp, p0)

                    loss, grads = jax.value_and_grad(loss_of)(p)
                    new_a = a
                else:

                    def loss_of(pp):
                        loss, new_a = batch_loss(
                            {"params": pp, **a}, x, y, True, list(a)
                        )
                        if kind == "scaffold":
                            return loss, new_a
                        return loss + prox(pp, p0), new_a

                    (loss, new_a), grads = jax.value_and_grad(
                        loss_of, has_aux=True
                    )(p)
                with jax.named_scope("tpfl.optimizer"):
                    if kind == "scaffold":
                        grads = jax.tree_util.tree_map(
                            lambda g, c: g + c.astype(g.dtype), grads, corr
                        )
                    updates, o = opt.update(grads, o, p)
                    p = optax.apply_updates(p, updates)
                return (p, o, new_a), loss

            if epochs <= 0:  # static: aggregation-only round
                variables = {"params": params, **aux} if kind != "plain" else {
                    "params": params
                }
                logits = module.apply(variables, xb[0], train=False)
                return params, c_i, aux, loss_fn(logits, yb[0]).mean()

            def epoch_body(_, carry):
                p, o, a, _last = carry
                (p, o, a), losses = lax.scan(batch_step, (p, o, a), (xb, yb))
                # Thread the epoch's mean loss through the carry — no
                # extra forward pass after the loop.
                return (p, o, a, jnp.mean(losses))

            params, opt_state, aux, loss = lax.fori_loop(
                0, epochs, epoch_body,
                (params, opt_state, aux, jnp.float32(0)),
            )
            if kind == "scaffold":
                # Option II: c_i+ = c_i - c + (x - y)/(K·lr)
                k_steps = epochs * xb.shape[0]
                scale = 1.0 / max(k_steps * lr, 1e-12)
                c_i = jax.tree_util.tree_map(
                    lambda ci, cg, x0, y_: (
                        ci.astype(jnp.float32)
                        - cg.astype(jnp.float32)
                        + scale
                        * (x0.astype(jnp.float32) - y_.astype(jnp.float32))
                    ).astype(ci.dtype),
                    c_i, c_g, p0, params,
                )
            return params, c_i, aux, loss

        return local_train

    @staticmethod
    def _fold_weights(weights, valid, psum_axis, host_axis=None):
        """Normalized fold weights: ``weights / Σweights`` with a
        uniform-over-REAL-nodes fallback when all-zero (pad rows never
        enter the fallback). Sums are global — on a sharded mesh each
        device's partial sum is psum-reduced over the ``nodes`` axis
        (the first collective of the gossip exchange), then over
        ``hosts`` on a 3D mesh (scalar DCN traffic — the weight mass
        never rides a codec)."""
        total = jnp.sum(weights)
        valid_total = jnp.sum(valid)
        if psum_axis is not None:
            total = lax.psum(total, psum_axis)
            valid_total = lax.psum(valid_total, psum_axis)
        if host_axis is not None:
            total = lax.psum(total, host_axis)
            valid_total = lax.psum(valid_total, host_axis)
        fallback = valid / jnp.maximum(valid_total, 1.0)
        return jnp.where(
            total > 0, weights / jnp.maximum(total, 1e-9), fallback
        )

    def _build_fold(
        self, kind: str, psum_axis: Optional[str],
        host_axis: Optional[str] = None,
        dcn_codec: Optional[Callable] = None,
    ) -> Callable:
        """Masked FedAvg fold + full-model diffusion (+ the SCAFFOLD
        server update / aux aggregation). ``psum_axis`` None = the
        single-program einsum over the whole node axis (the legacy
        ``VmapFederation`` reduction); set = per-device partial sums
        all-reduced by ``lax.psum`` — gossip as a mesh collective.

        ``host_axis`` (3D meshes) decomposes the reduction in two
        legs: the ``nodes`` psum folds each host's local partial over
        ICI, then the partial aggregates all-reduce over ``hosts`` —
        the DCN leg. Exact at any host count: a psum over a product of
        axes equals psums over each in sequence. ``dcn_codec`` (the
        ENGINE_WIRE_CODEC lowered onto DCN) round-trips each host's
        PARAMS partial through the wire codec between the two legs, so
        the cross-host traffic ships int8/sparse natively — params
        only, like the node-level exchange codec: SCAFFOLD variates
        and aux stats cross dense."""
        aux_mode = self.aux_mode
        n_logical = self.n_nodes

        def leaf_mean_of(wnorm, on_wire=False):
            def leaf_mean(p):
                w = wnorm.astype(jnp.float32)
                # Masked-out (w=0) nodes are zeroed BEFORE the
                # reduction — a w=0 node whose params overflowed would
                # otherwise contribute 0 * inf = NaN.
                sel = w.reshape((-1,) + (1,) * (p.ndim - 1)) > 0
                clean = jnp.where(sel, p.astype(jnp.float32), 0.0)
                agg = jnp.einsum("n,n...->...", w, clean)
                if psum_axis is not None:
                    agg = lax.psum(agg, psum_axis)
                if host_axis is not None:
                    if on_wire and dcn_codec is not None:
                        # The host's partial aggregate passes the wire
                        # round-trip BEFORE the DCN all-reduce — every
                        # peer host folds what the wire would deliver.
                        agg = dcn_codec(agg)
                    agg = lax.psum(agg, host_axis)
                return agg.astype(p.dtype)

            return leaf_mean

        def diffuse(tree, wnorm, n_local, on_wire=False):
            leaf_mean = leaf_mean_of(wnorm, on_wire)
            agg = jax.tree_util.tree_map(leaf_mean, tree)
            # Every node receives the aggregate (the FullModelCommand
            # equivalent of the protocol path) — on a mesh this is the
            # broadcast leg of the gossip collective.
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n_local, *a.shape)), agg
            )

        def fold(trained, new_c, new_aux, c_locals, c_global, aux, weights,
                 valid):
            n_local = weights.shape[0]
            wnorm = self._fold_weights(weights, valid, psum_axis, host_axis)
            out_params = diffuse(trained, wnorm, n_local, on_wire=True)
            sel = weights > 0

            def keep_elected(new, old):
                return jnp.where(
                    sel.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
                )

            if kind == "scaffold":
                out_c = jax.tree_util.tree_map(keep_elected, new_c, c_locals)
                # c += (|S|/N) · mean over ELECTED of delta_c (uniform
                # mean per the paper, N = LOGICAL federation size —
                # pad rows are never elected).
                mask = sel.astype(jnp.float32)
                elected = jnp.sum(mask)
                if psum_axis is not None:
                    elected = lax.psum(elected, psum_axis)
                if host_axis is not None:
                    elected = lax.psum(elected, host_axis)
                um = self._fold_weights(mask, valid, psum_axis, host_axis)
                uniform_mean = leaf_mean_of(um)
                frac = elected / n_logical
                out_cg = jax.tree_util.tree_map(
                    lambda cg, dcm: (
                        cg.astype(jnp.float32) + frac * dcm.astype(jnp.float32)
                    ).astype(cg.dtype),
                    c_global,
                    jax.tree_util.tree_map(
                        lambda n, o: uniform_mean(
                            n.astype(jnp.float32) - o.astype(jnp.float32)
                        ),
                        new_c, c_locals,
                    ),
                )
            else:
                out_c, out_cg = c_locals, c_global
            if kind == "plain":
                out_aux = aux
            elif aux_mode == "local":
                # FedBN: stats stay per-node — but a w=0 node did not
                # participate, so its private stats must not advance.
                out_aux = jax.tree_util.tree_map(keep_elected, new_aux, aux)
            else:
                out_aux = diffuse(new_aux, wnorm, n_local)
            return out_params, out_c, out_cg, out_aux

        return fold

    def _build_multi(
        self, kind: str, epochs: int, n_rounds: int, w_ndim: int,
        telemetry: bool = False, a_ndim: int = 0, codec: int = 0,
        topk_frac: float = 0.05, fedbuff: bool = False,
        stale_exp: float = 0.0,
    ) -> Callable:
        """The UNJITTED federation program (shard_map-wrapped on a
        mesh): ``fn(params, c_locals, c_global, aux, xs, ys, weights,
        valid) -> (params, c_locals, c_global, aux, losses)`` with
        ``epochs`` and ``n_rounds`` baked in. One round is local train
        (vmap) + fold; ``n_rounds > 1`` wraps it in a device-side
        fori_loop so the dispatch RTT is paid once per window.
        ``VmapFederation``'s builders trace this inside their own jits
        (keeping ``.lower()`` and the legacy donation signatures);
        :meth:`program` jits it directly.

        ``telemetry`` (the ``ENGINE_TELEMETRY`` variant) threads a
        fixed-shape ``[n_rounds, ...]`` buffer dict through the loop
        carry — :data:`TELEMETRY_FIELDS`, appended as a SIXTH output —
        computed from values the round body already holds (the trained
        params, the round-start params, the fold output, the weights):
        no extra HBM traffic, and collectives only where the fold
        already psums. ``telemetry=False`` lowers the byte-identical
        program of the pre-telemetry path: every telemetry branch below
        is Python-level, so the carry is elided from the trace, not
        masked out of it.

        ``a_ndim`` (the adversarial variant; only tests pass it —
        ``tests/test_engine_obs.py`` — kept until ROADMAP D3):
        appends an ``attack_scales`` argument ([n] or [n_rounds, n])
        multiplied into each node's TRAINED params before stats and
        fold — the in-program lowering of ``AttackPlan``'s sign-flip
        schedule (``scale = 1 − 2α``), so the telemetry carry observes
        engine-tier adversaries exactly where the gRPC tier's ledger
        observes protocol-tier ones.

        ``codec`` (the ``ENGINE_WIRE_CODEC`` variant): a device-side
        wire codec for the gossip exchange — each node's trained
        params pass the per-leaf quantize→dequantize (int8) or top-k
        mask round-trip IN-PROGRAM before the fold's psum, so the
        exchange leg ships int8/sparse tensors over ICI/DCN natively
        (``tpfl.learning.compression.engine_codec_roundtrip``, vmapped
        over the node axis: every node quantizes its own payload, and
        telemetry stats observe what a receiver would decode — the
        gRPC tier's intake semantics). Params only: aux stats and
        SCAFFOLD variates ride dense (per-node state, not the model
        payload). ``codec=0`` is Python-level elision like
        ``telemetry=False`` — the dense program lowers byte-identical
        to the pre-codec path. The telemetry carry's ``wire_bytes``
        row is the exchange's per-round tensor payload bytes
        (participating nodes × the codec's per-model bytes,
        ``compression.wire_bytes_per_model``) computed device-side.

        ``fedbuff`` (the async-window variant, with ``stale_exp`` =
        the resolved ``ASYNC_STALENESS_EXP``): appends ``arrivals``
        and ``taus`` arguments (``[n_rounds, n]`` each, from a
        :class:`FedBuffSchedule`). Per round, a node's fold weight
        becomes ``w · arrive · (1+τ)^-stale_exp`` — the gRPC
        aggregator's ``staleness_weight`` lowered on device, bit-equal
        at τ=0 — and only ARRIVING nodes take the fold broadcast:
        stragglers keep their locally-trained params/variates/aux (the
        buffered-async semantics: their accumulated update arrives,
        staleness-weighted, at a later round). ``fedbuff=False`` is
        Python-level elision like ``telemetry=False`` — the sync
        program lowers byte-identical to the pre-fedbuff path. With
        telemetry, the carry grows a per-node
        :data:`TELEMETRY_STALENESS_FIELD` row (τ on arrival rounds,
        −1 otherwise)."""
        local_train = self._build_local_train(kind)
        mesh = self.mesh
        # Manual shard_map (per-device code, explicit psum over the
        # node axis) on 1D node meshes — the byte-pinned pre-2D
        # lowering. 2D nodes x model meshes take the GSPMD route
        # instead: the SAME un-wrapped program, partitioned by XLA
        # from the per-leaf layout shardings — the fold's einsum over
        # the node axis still lowers to an all-reduce over ``nodes``
        # only, with each model shard folding its own slice, and the
        # layout's TP/FSDP collectives come from sharding propagation
        # (the scaling-book recipe; a hand-written manual-TP body
        # would re-derive what the partitioner already proves).
        sharded = (
            mesh is not None
            and node_shard_size(mesh) > 1
            and self.model_axes <= 1
        )
        psum_axis = NODE_AXIS if sharded else None
        # 3D meshes split the fold's reduction in two legs: nodes
        # (ICI, above) then hosts (DCN) — with the wire codec lowered
        # onto the DCN leg (see _build_fold). hosts == 1 leaves
        # host_axis None, so every cross-host branch below is elided
        # at the Python level and 1D/2D programs lower byte-identical
        # to the single-host engine.
        hosts = mesh_axis_size(mesh, HOST_AXIS) if sharded else 1
        host_axis = HOST_AXIS if hosts > 1 else None
        codec_fn = compression.engine_codec_roundtrip(codec, topk_frac)
        fold = self._build_fold(
            kind, psum_axis, host_axis, codec_fn if codec else None
        )
        f32 = jnp.float32

        def per_node_sq(tree):
            """Σ over leaves/features per node row -> [n_local]."""
            total = jnp.zeros((), f32)
            for leaf in jax.tree_util.tree_leaves(tree):
                total = total + jnp.sum(
                    leaf.astype(f32).reshape(leaf.shape[0], -1) ** 2, axis=1
                )
            return total

        def per_node_dot(a, b):
            total = jnp.zeros((), f32)
            for x, y in zip(
                jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
            ):
                total = total + jnp.sum(
                    (x.astype(f32) * y.astype(f32)).reshape(x.shape[0], -1),
                    axis=1,
                )
            return total

        def psum_(x):
            if psum_axis is not None:
                x = lax.psum(x, psum_axis)
            if host_axis is not None:
                x = lax.psum(x, host_axis)
            return x

        def masked_mean(x, valid):
            num = psum_(jnp.sum(x * valid))
            den = psum_(jnp.sum(valid))
            return num / jnp.maximum(den, 1.0)

        def round_body(params, c_locals, c_global, aux, xs, ys, w, valid,
                       scale, arrive, tau):
            with jax.named_scope("tpfl.train"):
                trained, new_c, new_aux, losses = jax.vmap(
                    lambda p, ci, a, x, y: local_train(
                        p, ci, c_global, a, x, y, epochs
                    )
                )(params, c_locals, aux, xs, ys)
            if fedbuff:
                # FedBuff intake: only ARRIVING nodes fold this round,
                # each weighted by the gRPC aggregator's staleness
                # schedule w(τ) = 1/(1+τ)^exp (aggregator.py
                # staleness_weight — bit-equal at τ=0, where both
                # sides produce exactly 1.0).
                sw = (1.0 + tau) ** f32(-stale_exp)
                w = w * arrive * sw
            if a_ndim:
                # Under the exchange leg's scope: what a node puts on
                # the wire is scaled here, before the codec sees it.
                with jax.named_scope("tpfl.codec"):
                    trained = jax.tree_util.tree_map(
                        lambda t: (
                            scale.reshape((-1,) + (1,) * (t.ndim - 1)).astype(
                                t.dtype
                            )
                            * t
                        ),
                        trained,
                    )
            if codec:
                # The exchange leg: every node's contribution passes
                # the wire round-trip BEFORE stats and fold, so the
                # telemetry carry and the psum both see exactly what a
                # receiver would decode.
                with jax.named_scope("tpfl.codec"):
                    trained = jax.tree_util.tree_map(
                        lambda t: jax.vmap(codec_fn)(t), trained
                    )
            if telemetry:
                with jax.named_scope("tpfl.telemetry"):
                    upd = jax.tree_util.tree_map(
                        lambda t, p: t.astype(f32) - p.astype(f32),
                        trained, params,
                    )
                    t_sq = per_node_sq(trained)
                    s_sq = per_node_sq(params)
                    node_stats = {
                        "update_norm": jnp.sqrt(per_node_sq(upd)),
                        "cos_ref": per_node_dot(trained, params)
                        / jnp.sqrt(jnp.maximum(t_sq * s_sq, 1e-12)),
                    }
                    if fedbuff:
                        # τ on arrival rounds, −1 on in-flight rounds —
                        # so the host fan-out distinguishes "arrived
                        # fresh" (τ=0) from "did not arrive".
                        node_stats["staleness"] = (
                            tau * arrive - (1.0 - arrive)
                        )
            with jax.named_scope("tpfl.fold"):
                out_params, out_c, out_cg, out_aux = fold(
                    trained, new_c, new_aux, c_locals, c_global, aux, w,
                    valid,
                )
            if fedbuff:
                # Only arrivals take the fold broadcast; stragglers
                # keep their local training (params, variates, aux) —
                # their buffered update folds at a later arrival.
                got = arrive > 0

                def took_fold(new, local):
                    return jnp.where(
                        got.reshape((-1,) + (1,) * (new.ndim - 1)),
                        new, local,
                    )

                with jax.named_scope("tpfl.fold"):
                    out_params = jax.tree_util.tree_map(
                        took_fold, out_params, trained
                    )
                    if kind == "scaffold":
                        out_c = jax.tree_util.tree_map(
                            took_fold, out_c, new_c
                        )
                    if kind != "plain":
                        out_aux = jax.tree_util.tree_map(
                            took_fold, out_aux, new_aux
                        )
            if telemetry:
                with jax.named_scope("tpfl.telemetry"):
                    # out_params rows are IDENTICAL by construction (the
                    # fold broadcasts the aggregate to every node), so the
                    # global-model stats need one row per device, not the
                    # full [n, P] sweep: row 0 of each local shard,
                    # mean-reduced over devices by the same masked-mean
                    # machinery (all devices hold the same aggregate; their
                    # round-start rows coincide after the first fold).
                    first = valid * (
                        jnp.arange(valid.shape[0]) == 0
                    ).astype(f32)
                    moved_sq = jnp.zeros((), f32)
                    out_sq = jnp.zeros((), f32)
                    for o, p in zip(
                        jax.tree_util.tree_leaves(out_params),
                        jax.tree_util.tree_leaves(params),
                    ):
                        o0 = o[0].astype(f32)
                        p0 = p[0].astype(f32)
                        moved_sq = moved_sq + jnp.sum((o0 - p0) ** 2)
                        out_sq = out_sq + jnp.sum(o0 * o0)
                    zero = jnp.zeros((valid.shape[0],), f32)
                    participation = psum_(jnp.sum((w > 0).astype(f32)))
                    # Per-node wire payload bytes under the active codec —
                    # a static constant of the leaf shapes (computed at
                    # trace time from the SAME per-leaf policy the host
                    # payload path applies); the per-round series is
                    # participation-dependent and rides the carry.
                    bpm = compression.wire_bytes_per_model(
                        jax.tree_util.tree_map(
                            lambda t: jax.ShapeDtypeStruct(
                                t.shape[1:], t.dtype
                            ),
                            trained,
                        ),
                        codec, topk_frac,
                    )
                    round_stats = {
                        "delta_norm": masked_mean(
                            zero.at[0].set(jnp.sqrt(moved_sq)), first
                        ),
                        "model_norm": masked_mean(
                            zero.at[0].set(jnp.sqrt(out_sq)), first
                        ),
                        "participation": participation,
                        "weight_mass": psum_(jnp.sum(w.astype(f32))),
                        "wire_bytes": participation * f32(bpm),
                    }
                    if host_axis is not None:
                        # The DCN leg ships ONE model-shaped partial per
                        # host per round (the fold's cross-host
                        # all-reduce), codec'd like the node exchange —
                        # same per-model bytes constant, hosts copies.
                        round_stats["dcn_bytes"] = f32(hosts) * f32(bpm)
                return (
                    out_params, out_c, out_cg, out_aux, losses,
                    (node_stats, round_stats),
                )
            return out_params, out_c, out_cg, out_aux, losses

        def tele_init(n_local):
            per_node = jnp.zeros((n_rounds, n_local), f32)
            per_round = jnp.zeros((n_rounds,), f32)
            tele = {
                "loss": per_node,
                "update_norm": per_node,
                "cos_ref": per_node,
            }
            if fedbuff:
                tele["staleness"] = per_node
            tele.update(
                {
                    "delta_norm": per_round,
                    "model_norm": per_round,
                    "participation": per_round,
                    "weight_mass": per_round,
                    "wire_bytes": per_round,
                }
            )
            if host_axis is not None:
                tele["dcn_bytes"] = per_round
            return tele

        def tele_write(tele, r, losses, node_stats, round_stats):
            tele = dict(tele)
            with jax.named_scope("tpfl.telemetry"):
                tele["loss"] = tele["loss"].at[r].set(losses.astype(f32))
                for k, v in node_stats.items():
                    tele[k] = tele[k].at[r].set(v)
                for k, v in round_stats.items():
                    tele[k] = tele[k].at[r].set(v)
            return tele

        # The function's name is the XLA module's (``jit_tpfl_window``):
        # what a profiler trace lists the window under. It is also the
        # only part of the named scopes' change that the persistent
        # compile cache can see — scope names are debug metadata, which
        # JAX strips from the cache key, so under the old name a warm
        # cache would serve the executable compiled BEFORE the scopes
        # existed, with none of them in it (PERF.md, PR 24).
        def tpfl_window(params, c_locals, c_global, aux, xs, ys, weights,
                        valid, *extra):
            extra = list(extra)
            scales = extra.pop(0) if a_ndim else None
            arrivals, taus = (
                (extra[0], extra[1]) if fedbuff else (None, None)
            )

            def scale_for(r):
                if not a_ndim:
                    return None
                return scales if a_ndim == 1 else scales[r]

            def sched_for(r):
                if not fedbuff:
                    return None, None
                return arrivals[r], taus[r]

            if n_rounds == 1:
                w = weights if w_ndim == 1 else weights[0]
                out = round_body(
                    params, c_locals, c_global, aux, xs, ys, w, valid,
                    scale_for(0), *sched_for(0),
                )
                if telemetry:
                    p, ci, cg, a, losses, (ns_, rs_) = out
                    tele = tele_write(
                        tele_init(valid.shape[0]), 0, losses, ns_, rs_
                    )
                    return p, ci, cg, a, losses, tele
                return out

            def body(r, carry):
                if telemetry:
                    p, ci, cg, a, _, tele = carry
                else:
                    p, ci, cg, a, _ = carry
                w = weights if w_ndim == 1 else weights[r]
                out = round_body(
                    p, ci, cg, a, xs, ys, w, valid, scale_for(r),
                    *sched_for(r),
                )
                if telemetry:
                    p, ci, cg, a, losses, (ns_, rs_) = out
                    return p, ci, cg, a, losses, tele_write(
                        tele, r, losses, ns_, rs_
                    )
                return out

            init_losses = jnp.zeros((valid.shape[0],), jnp.float32)
            init = (params, c_locals, c_global, aux, init_losses)
            if telemetry:
                init = init + (tele_init(valid.shape[0]),)
            return lax.fori_loop(0, n_rounds, body, init)

        if not sharded:
            return tpfl_window

        if host_axis is not None:
            # 3D mesh: the stacked node axis shards over hosts x nodes
            # combined — each host's devices hold a contiguous run of
            # logical nodes (the same placement federation_sharding
            # commits the buffers to).
            node = PartitionSpec((HOST_AXIS, NODE_AXIS))
            rn = PartitionSpec(None, (HOST_AXIS, NODE_AXIS))
        else:
            node = PartitionSpec(NODE_AXIS)
            rn = PartitionSpec(None, NODE_AXIS)
        repl = PartitionSpec()
        w_spec = node if w_ndim == 1 else rn
        in_specs = [node, node, repl, node, node, node, w_spec, node]
        if a_ndim:
            in_specs.append(node if a_ndim == 1 else rn)
        if fedbuff:
            in_specs += [rn, rn]
        out_specs: tuple = (node, node, repl, node, node)
        if telemetry:
            tele_specs = {
                "loss": rn,
                "update_norm": rn,
                "cos_ref": rn,
                "delta_norm": repl,
                "model_norm": repl,
                "participation": repl,
                "weight_mass": repl,
                "wire_bytes": repl,
            }
            if fedbuff:
                tele_specs["staleness"] = rn
            if host_axis is not None:
                tele_specs["dcn_bytes"] = repl
            out_specs = out_specs + (tele_specs,)
        return shard_map(
            tpfl_window,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            check_vma=False,
        )

    def raw_program(
        self, kind: str, epochs: int, n_rounds: int = 1, w_ndim: int = 1,
        codec: int = 0, topk_frac: float = 0.05,
        model_axes: int = 1, layout: str = "replicated",
        fedbuff: bool = False, stale_exp: float = 0.0,
    ) -> Callable:
        """Cached UNJITTED program (shard_map-wrapped on a 1D mesh)
        for tracing inside a caller's own jit. ``codec`` selects the
        device-side wire-codec variant, ``model_axes``/``layout`` the
        2D-mesh variant, ``fedbuff``/``stale_exp`` the async-window
        variant (separate cache slots — the same key hygiene as the
        jitted programs; pass the engine's own
        ``self.model_axes``/``self.layout.name``)."""
        key = (
            "raw", kind, int(epochs), int(n_rounds), int(w_ndim),
            int(codec), float(topk_frac), int(model_axes), str(layout),
            bool(fedbuff), float(stale_exp),
        )
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = self._build_multi(
                kind, int(epochs), int(n_rounds), int(w_ndim),
                codec=int(codec), topk_frac=float(topk_frac),
                fedbuff=bool(fedbuff), stale_exp=float(stale_exp),
            )
        return fn

    def _model_mesh_shardings(
        self, w_ndim: int, telemetry: bool, a_ndim: int,
        fedbuff: bool = False,
    ) -> "tuple[tuple, tuple] | tuple[None, None]":
        """(in_shardings, out_shardings) for the 2D GSPMD program —
        the per-leaf layout shardings of the CURRENT dispatch's placed
        args (``_prepare_args`` stashes them; the engine is
        single-owner, so the stash always describes the dispatch that
        is about to fetch the program). Explicit shardings matter for
        more than placement: buffer DONATION is resolved at lowering,
        and a jit that only infers shardings from committed inputs
        marks donated leaves ``jax.buffer_donor`` (freed) instead of
        aliasing them into the outputs. (None, None) before any
        dispatch — the inferred-sharding fallback for direct
        ``program()`` inspection calls."""
        in_sh = self._arg_shardings
        if in_sh is None:
            return None, None
        mesh = self.mesh
        ns = federation_sharding(mesh)
        out_sh: tuple = (in_sh[0], in_sh[1], in_sh[2], in_sh[3], ns)
        if telemetry:
            rn = _round_node_sharding(mesh)
            rs = replicated(mesh)
            tele_sh = {
                "loss": rn,
                "update_norm": rn,
                "cos_ref": rn,
                "delta_norm": rs,
                "model_norm": rs,
                "participation": rs,
                "weight_mass": rs,
                "wire_bytes": rs,
            }
            if fedbuff:
                tele_sh["staleness"] = rn
            out_sh = out_sh + (tele_sh,)
        return tuple(in_sh), out_sh

    def _build_program(
        self, kind: str, epochs: int, n_rounds: int, w_ndim: int,
        donate: bool = True, telemetry: bool = False, a_ndim: int = 0,
        codec: int = 0, topk_frac: float = 0.05,
        model_axes: int = 1, layout: str = "replicated",
        fedbuff: bool = False, stale_exp: float = 0.0,
        capacity: int = 0, mesh_nodes: int = 1,
        mesh_hosts: int = 1, pop_size: int = 0,
    ) -> Callable:
        # capacity / mesh_nodes / mesh_hosts / pop_size are pure
        # cache-key axes: the padded tier and mesh shape (hosts axis
        # included) already determine the abstract shapes and the
        # shard_map lowering this build closes over, and the
        # population census determines the sampled cohort the caller
        # stacked — none re-enters the trace.
        del capacity, mesh_nodes, mesh_hosts, pop_size
        multi = self._build_multi(
            kind, epochs, n_rounds, w_ndim, telemetry, a_ndim, codec,
            topk_frac, fedbuff, stale_exp,
        )
        dn = (0, 1, 2, 3) if donate else ()
        mesh = self.mesh
        if mesh is None or (
            node_shard_size(mesh) <= 1 and self.model_axes <= 1
        ):
            return jax.jit(multi, donate_argnums=dn)
        if self.model_axes > 1:
            # 2D nodes x model: the un-wrapped program under GSPMD —
            # per-leaf layout shardings in and out, collectives
            # inserted by the partitioner (see _build_multi).
            in_sh, out_sh = self._model_mesh_shardings(
                w_ndim, telemetry, a_ndim, fedbuff
            )
            if in_sh is None:
                return jax.jit(multi, donate_argnums=dn)
            return jax.jit(
                multi, donate_argnums=dn, in_shardings=in_sh,
                out_shardings=out_sh,
            )
        ns = federation_sharding(mesh)
        rs = replicated(mesh)
        rn = _round_node_sharding(mesh)
        ws = ns if w_ndim == 1 else rn
        in_sh = [ns, ns, rs, ns, ns, ns, ws, ns]
        if a_ndim:
            in_sh.append(ns if a_ndim == 1 else rn)
        if fedbuff:
            in_sh += [rn, rn]
        out_sh: tuple = (ns, ns, rs, ns, ns)
        if telemetry:
            tele_sh = {
                "loss": rn,
                "update_norm": rn,
                "cos_ref": rn,
                "delta_norm": rs,
                "model_norm": rs,
                "participation": rs,
                "weight_mass": rs,
                "wire_bytes": rs,
            }
            if fedbuff:
                tele_sh["staleness"] = rn
            if mesh_axis_size(mesh, HOST_AXIS) > 1:
                tele_sh["dcn_bytes"] = rs
            out_sh = out_sh + (tele_sh,)
        return jax.jit(
            multi,
            donate_argnums=dn,
            in_shardings=tuple(in_sh),
            out_shardings=out_sh,
        )

    def program(
        self, kind: str, epochs: int, n_rounds: int = 1, w_ndim: int = 1,
        donate: bool = True, telemetry: bool = False, a_ndim: int = 0,
        codec: int = 0, topk_frac: float = 0.05,
        model_axes: int = 1, layout: str = "replicated",
        fedbuff: bool = False, stale_exp: float = 0.0,
        capacity: int = 0, mesh_nodes: int = 1,
        mesh_hosts: int = 1, pop_size: int = 0,
    ) -> Callable:
        """Cached compiled program for ``(kind, epochs, n_rounds,
        w_ndim)`` — the raw jitted callable. ``donate=False`` builds a
        NON-donating variant (separate cache slot) for a caller that
        re-feeds inputs a donating program would have consumed: the
        tests' ``.lower()`` pins, ``benchmark/harness.py``'s check and
        ``chip_smoke.py``'s ``sync`` phase pass it (ROADMAP D3).
        ``telemetry``/``a_ndim``/``codec`` select the ENGINE_TELEMETRY
        carry / attack-scale / ENGINE_WIRE_CODEC variants — every
        variant axis (donation mode included) is part of the cache
        key, so toggling a knob between windows never mutates an
        already-compiled program: the disabled program stays the
        byte-identical pre-telemetry (and pre-codec) lowering.
        ``topk_frac`` is in the key because top-k's ``k`` is a static
        constant of the compiled program. ``model_axes``/``layout``
        (the SHARD_MODEL / SHARD_LAYOUT axes — fixed per engine, but a
        key axis all the same, like ``donate``) split the 2D GSPMD
        lowering from the 1D manual one. ``fedbuff``/``stale_exp``
        (the async-window variant and its resolved
        ``ASYNC_STALENESS_EXP``) are key axes too: the staleness
        exponent is a trace-time constant of the fold weighting, so
        flipping the knob between windows must select a different
        compiled program. ``capacity``/``mesh_nodes`` (the ISSUE-17
        elastic axes: the padded capacity tier the program is shaped
        for, and the mesh's node-axis size the shard_map lowering
        closed over) make the elastic/resume contract explicit in the
        key: a tier promotion or a restore onto a different mesh shape
        selects its own slot — and DEMOTING back to a seen tier is a
        cache hit, so tier oscillation compiles each tier once.
        ``mesh_hosts``/``pop_size`` (the ISSUE-18 cross-host /
        cross-device axes: the mesh's ``hosts``-axis size the
        two-level psum lowering closed over, and the registered
        population census the dispatched cohort was sampled from)
        follow the same discipline — a hosts-axis change or a
        population attach/detach selects its own slot."""
        key = (
            kind, int(epochs), int(n_rounds), int(w_ndim), bool(donate),
            bool(telemetry), int(a_ndim), int(codec), float(topk_frac),
            int(model_axes), str(layout), bool(fedbuff), float(stale_exp),
            int(capacity), int(mesh_nodes),
            int(mesh_hosts), int(pop_size),
        )
        fn = self._programs.get(key)
        profiling.observatory.cache_event("engine_programs", hit=fn is not None)
        if fn is None:
            fn = self._programs[key] = self._build_program(*key)
        return fn

    def _wrapped_program(
        self, kind: str, epochs: int, n_rounds: int, w_ndim: int,
        donate: bool = True, telemetry: bool = False, a_ndim: int = 0,
        codec: int = 0, topk_frac: float = 0.05,
        model_axes: int = 1, layout: str = "replicated",
        fedbuff: bool = False, stale_exp: float = 0.0,
        capacity: int = 0, mesh_nodes: int = 1,
        mesh_hosts: int = 1, pop_size: int = 0,
    ) -> Callable:
        """The same program behind the compile observatory's recompile
        detection (keyed per (engine program, abstract shapes) like
        every other jit seam). Variant programs get their own names —
        the telemetry/attack/codec/2D-mesh/fedbuff (and capacity-tier
        / hosts-axis / population) signatures differ by construction
        and must not read as recompile storms of the base program.
        A program built here leaves its observatory name in
        ``_fresh_program`` until the dispatch that fetched it takes it
        for its ``first_call`` row of the set-up account."""
        key = (
            kind, int(epochs), int(n_rounds), int(w_ndim), bool(donate),
            bool(telemetry), int(a_ndim), int(codec), float(topk_frac),
            int(model_axes), str(layout), bool(fedbuff), float(stale_exp),
            int(capacity), int(mesh_nodes),
            int(mesh_hosts), int(pop_size),
        )
        fn = self._wrapped.get(key)
        if fn is None:
            suffix = (
                (":obs" if telemetry else "")
                + (":atk" if a_ndim else "")
                + (f":{compression.codec_name(codec)}" if codec else "")
                + (f":m{int(model_axes)}" if int(model_axes) > 1 else "")
                + (":fb" if fedbuff else "")
                + (f":c{int(capacity)}" if capacity else "")
                + (f":h{int(mesh_hosts)}" if int(mesh_hosts) > 1 else "")
                + (f":pop{int(pop_size)}" if pop_size else "")
                + (":hl" if self.head_owns_loss else "")
            )
            self._fresh_program = (
                f"engine_round:{kind}x{n_rounds}{suffix}:"
                f"{profiling.module_tag(self.module)}"
            )
            wrapped = profiling.observatory.wrap(
                self.program(*key), self._fresh_program
            )
            # TRACE_CONTRACTS (off = no wrapper): stamp the program
            # with the knob values its cache key encodes, so a future
            # key-hygiene bug fails at dispatch with a named witness
            # instead of silently serving this program under other
            # knob values (tpfl.concurrency, the capture pass's
            # runtime half).
            fn = self._wrapped[key] = concurrency.stamp_contract(
                wrapped,
                {
                    "ENGINE_TELEMETRY": bool(telemetry),
                    "ENGINE_WIRE_CODEC": int(codec),
                    "WIRE_TOPK_FRAC": float(topk_frac),
                    "ENGINE_DONATE": bool(donate),
                    "SHARD_MODEL": int(model_axes),
                    "SHARD_LAYOUT": str(layout),
                    # 0.0 for sync programs — the dispatch side resolves
                    # the knob to 0.0 when no schedule rides the window,
                    # so the contract stays total without forcing the
                    # sync path to track an async-only knob.
                    "ASYNC_STALENESS_EXP": float(stale_exp),
                    "SHARD_HOSTS": int(mesh_hosts),
                    "POPULATION_CLIENTS": int(pop_size),
                },
            )
        return fn

    # --- execution -------------------------------------------------------

    def _resolve_variant(self) -> tuple[bool, int, float]:
        """(telemetry, codec bits, top-k fraction) from the Settings
        knobs — read per dispatch and folded into the program cache
        key, so a knob flip between windows selects a different cache
        slot instead of mutating a compiled program."""
        return (
            bool(Settings.ENGINE_TELEMETRY),
            compression.resolve_engine_codec(Settings.ENGINE_WIRE_CODEC),
            float(Settings.WIRE_TOPK_FRAC),
        )

    def _prepare_args(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any],
        n_rounds: int,
        aux: Optional[Any],
        scaffold_state: Optional[tuple[Any, Any]],
        attack_scales: Optional[Any],
        schedule: Optional[FedBuffSchedule] = None,
    ) -> tuple[str, list, Any, Optional[Any]]:
        """Pad, validate and PLACE one window's inputs — the one
        argument-prep path shared by :meth:`run_rounds` and
        :meth:`donation_report`, so the donation inspection can never
        drift from the buffers the real dispatch donates. Returns
        ``(kind, args, padded weights, padded attack scales)``;
        ``schedule`` (the fedbuff variant) appends its padded
        arrivals/taus arrays to ``args``."""
        kind = self._kind(aux)
        if kind == "scaffold" and scaffold_state is None:
            raise ValueError(
                "algorithm='scaffold' requires scaffold_state "
                "(init_scaffold_state(params))"
            )
        w = self.pad_weights(weights)
        if w.ndim == 2 and w.shape[0] != n_rounds:
            raise ValueError(
                f"per-round weights have {w.shape[0]} rows for "
                f"{n_rounds} rounds"
            )
        scales = None
        if attack_scales is not None:
            scales = self.pad_attack_scales(attack_scales)
            if scales.ndim == 2 and scales.shape[0] != n_rounds:
                raise ValueError(
                    f"per-round attack_scales have {scales.shape[0]} rows "
                    f"for {n_rounds} rounds"
                )
        arrivals = taus = None
        if schedule is not None:
            if schedule.n_rounds != n_rounds:
                raise ValueError(
                    f"schedule covers {schedule.n_rounds} rounds for a "
                    f"{n_rounds}-round window"
                )
            if schedule.n_nodes != self.n_nodes:
                raise ValueError(
                    f"schedule has {schedule.n_nodes} nodes for "
                    f"{self.n_nodes}"
                )
            extra = self.padded_nodes - self.n_nodes
            # host-sync: FedBuffSchedule holds host numpy arrays (built
            # before dispatch) — no device value is fetched here.
            arrivals = np.asarray(schedule.arrivals, np.float32)
            taus = np.asarray(schedule.taus, np.float32)  # host-sync: numpy
            if extra:
                # Pad rows never arrive (their fold weight is zero
                # regardless) and carry zero staleness.
                pad = np.zeros((n_rounds, extra), np.float32)
                arrivals = np.concatenate([arrivals, pad], axis=1)
                taus = np.concatenate([taus, pad], axis=1)
            arrivals = jnp.asarray(arrivals)
            taus = jnp.asarray(taus)
        # Explicit placement, not just padding: callers re-stacking from
        # a single global model (FederationLearner each protocol round)
        # hand in arrays COMMITTED as replicated on the mesh, which the
        # program's in_shardings would reject — device_put reshards
        # committed arrays where pjit refuses to. No-op (same buffer)
        # when the sharding already matches. Model-state trees go
        # through the layout-aware placement (node axis over ``nodes``,
        # leaf model dims over ``model`` on 2D meshes); data stays
        # node-axis-only — every model shard sees its node's full
        # batch.
        params = self._shard_state(self.pad_stacked(params))
        xs = self._shard(self.pad_stacked(xs))
        ys = self._shard(self.pad_stacked(ys))
        c_locals, c_global = ({}, {})
        if kind == "scaffold":
            c_locals, c_global = scaffold_state
            c_locals = self._shard_state(self.pad_stacked(c_locals))
            c_global = self._shard_global(c_global)
        a = {} if aux is None else self._shard_state(self.pad_stacked(aux))
        valid = self.valid
        if self.mesh is not None:
            w = global_put(
                w,
                federation_sharding(self.mesh)
                if w.ndim == 1
                else _round_node_sharding(self.mesh),
            )
            if scales is not None:
                scales = global_put(
                    scales,
                    federation_sharding(self.mesh)
                    if scales.ndim == 1
                    else _round_node_sharding(self.mesh),
                )
            if self.model_axes > 1 or is_multiprocess():
                # Multi-process runs place EVERY input explicitly:
                # a host-resident array reaching a jit whose sharding
                # spans non-addressable devices cannot be auto-placed.
                valid = global_put(valid, federation_sharding(self.mesh))
            if arrivals is not None:
                rn_sh = _round_node_sharding(self.mesh)
                arrivals = global_put(arrivals, rn_sh)
                taus = global_put(taus, rn_sh)
        args = [params, c_locals, c_global, a, xs, ys, w, valid]
        if scales is not None:
            args.append(scales)
        if arrivals is not None:
            args += [arrivals, taus]
        if self.model_axes > 1:
            # Stash the placed args' per-leaf shardings for the 2D
            # program builder (the lowering needs them explicitly for
            # donation aliasing — see _model_mesh_shardings).
            self._arg_shardings = tuple(
                jax.tree_util.tree_map(lambda x: x.sharding, arg)
                for arg in args
            )
        return kind, args, w, scales

    def _donating_program(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any] = None,
        epochs: int = 1,
        n_rounds: int = 1,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
    ) -> tuple[Callable, tuple]:
        """(the DONATING round program this engine would dispatch for
        these inputs, its placed args) — same ``_prepare_args`` path,
        same Settings-resolved telemetry/codec variant and cache key
        as :meth:`dispatch_window`, so an inspection can never drift
        from the program the real dispatch runs."""
        kind, args, w, _ = self._prepare_args(
            params, xs, ys, weights, n_rounds, aux, scaffold_state, None
        )
        tele_on, codec, frac = self._resolve_variant()
        fn = self.program(
            kind, epochs, n_rounds, w.ndim, donate=True,
            telemetry=tele_on, codec=codec, topk_frac=frac,
            model_axes=self.model_axes, layout=self.layout.name,
            capacity=int(self.padded_nodes),
            mesh_nodes=mesh_axis_size(self.mesh),
            mesh_hosts=mesh_axis_size(self.mesh, HOST_AXIS),
            pop_size=(
                0 if self.population is None
                else int(self.population.registered)
            ),
        )
        return fn, tuple(args)

    def donation_report(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any] = None,
        epochs: int = 1,
        n_rounds: int = 1,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
    ) -> dict:
        """Compiled-HLO buffer-donation inspection of the DONATING
        round program this engine would dispatch for these inputs:
        lowers and compiles the program and verifies every donated
        state leaf (params, SCAFFOLD variates, aux) is aliased to an
        output buffer end-to-end — the train+fold fusion costs no
        staging copy of the model state. See :func:`donation_analysis`
        for the report schema; CI gates ``clean``."""
        fn, args = self._donating_program(
            params, xs, ys, weights, epochs, n_rounds, aux, scaffold_state
        )
        return donation_analysis(fn, args)

    def compiled_hlo(
        self,
        params: Any,
        xs: Any,
        ys: Any,
        weights: Optional[Any] = None,
        epochs: int = 1,
        n_rounds: int = 1,
        aux: Optional[Any] = None,
        scaffold_state: Optional[tuple[Any, Any]] = None,
    ) -> str:
        """Compiled HLO text of that same donating program — what the
        backend's compiler actually emitted (``tpu_custom_call`` for a
        Pallas kernel, ``collective-permute`` / ``all-reduce`` for the
        mesh legs). Lowering executes nothing: the inputs survive."""
        fn, args = self._donating_program(
            params, xs, ys, weights, epochs, n_rounds, aux, scaffold_state
        )
        return fn.lower(*args).compile().as_text()

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
        """One federated round (``run_rounds`` with a window of 1 —
        the single-round program carries no loop wrapper, so it is the
        exact legacy ``VmapFederation.round`` computation)."""
        return self.run_rounds(
            params, xs, ys, weights=weights, epochs=epochs, n_rounds=1,
            aux=aux, scaffold_state=scaffold_state,
        )

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
        attack_scales: Optional[Any] = None,
        schedule: Optional[FedBuffSchedule] = None,
    ) -> tuple[Any, ...]:
        """Run ``n_rounds`` federation rounds in ONE device dispatch.

        ``weights``: [n] per-node FedAvg weight (0 = not elected),
        or [n_rounds, n] for per-round participation; None = uniform
        full participation. Data is reused across the window's rounds
        (the simulation semantics; re-stack between windows for
        fresh data). ``donate`` defaults to ``Settings.ENGINE_DONATE``
        (True: the program consumes the state buffers it was handed —
        params/variates/aux alias the outputs in-place, no staging
        copy; verify with :meth:`donation_report`); ``donate=False``
        keeps the input buffers alive (for a caller that re-feeds the
        same arrays: see :meth:`program`).

        With ``Settings.ENGINE_WIRE_CODEC`` != "dense" the window runs
        the device-codec program variant: every node's contribution
        passes the int8-quantize / top-k wire round-trip in-program
        before the gossip psum, and (with telemetry on) the carry's
        ``wire_bytes`` row records the exchange's per-round payload
        bytes. "dense" compiles the byte-identical pre-codec program.

        ``attack_scales`` ([n] or [n_rounds, n], test machinery):
        per-node multipliers applied to each node's TRAINED params
        before the fold — the in-program seeded adversary
        (``AttackPlan.engine_scales``); None (default) compiles no
        attack machinery at all.

        With ``Settings.ENGINE_TELEMETRY`` the window runs the
        telemetry-carry program variant and, at window close, fans the
        device-resident per-round stats out into the observatory planes
        (:mod:`tpfl.management.engine_obs`); the returned tuple is
        UNCHANGED — telemetry is read-only over the carry, and the
        model outputs stay byte-identical to the disabled program's.

        ``schedule`` (a :class:`FedBuffSchedule`): run the window's
        rounds ASYNC — per-round arrival masks gate which nodes fold,
        arrivals are staleness-weighted
        ``w(τ)=1/(1+τ)^ASYNC_STALENESS_EXP`` exactly like the gRPC
        aggregator, and stragglers keep local training instead of the
        broadcast. Seed-deterministic like everything else; None
        (default) compiles the byte-identical sync program.

        Returns (params, losses) — with ``aux`` (possibly ``{}``)
        (params, aux, losses) — and for algorithm="scaffold"
        (params, aux, (c_locals, c_global), losses), matching
        ``VmapFederation.round``. ``losses`` is the LAST round's
        per-node loss vector (padded length)."""
        return self.dispatch_window(
            params, xs, ys, weights=weights, epochs=epochs,
            n_rounds=n_rounds, aux=aux, scaffold_state=scaffold_state,
            donate=donate, attack_scales=attack_scales,
            schedule=schedule,
        ).finalize()

    def dispatch_window(
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
        attack_scales: Optional[Any] = None,
        schedule: Optional[FedBuffSchedule] = None,
    ) -> EngineWindow:
        """Dispatch one window WITHOUT blocking and return the
        :class:`EngineWindow` handle — the Sebulba split's device leg.
        The handle's outputs are async futures chainable straight into
        the next ``dispatch_window`` call; the host leg (profiler
        attribution, telemetry fan-out) runs at
        :meth:`EngineWindow.finalize`, which the pipeline overlaps
        with the next window's device time. :meth:`run_rounds` ==
        ``dispatch_window(...).finalize()``."""
        window_start = self._rounds_done
        with tracing.engine_span("dispatch", window_start):
            with tracing.engine_span("prepare_args", window_start):
                kind, args, w, scales = self._prepare_args(
                    params, xs, ys, weights, n_rounds, aux, scaffold_state,
                    attack_scales, schedule,
                )
            with tracing.engine_span("program_lookup", window_start):
                if donate is None:
                    donate = bool(Settings.ENGINE_DONATE)
                tele_on, codec, frac = self._resolve_variant()
                a_ndim = 0 if scales is None else int(scales.ndim)
                fedbuff = schedule is not None
                # Resolved at DISPATCH (0.0 for sync windows) and threaded into
                # the cache key: the staleness exponent is a trace-time
                # constant of the fedbuff fold weighting.
                stale_exp = (
                    float(Settings.ASYNC_STALENESS_EXP) if fedbuff else 0.0
                )
                model_axes, mesh_layout = self.model_axes, self.layout.name
                # The elastic key axes, resolved at dispatch like the knobs:
                # the padded capacity tier this window is shaped for, and the
                # mesh's node-axis size the lowering closed over — a tier
                # promotion or a restore onto another mesh shape must select
                # its own cache slot, never mutate a compiled program. The
                # cross-host / cross-device axes follow suit: the hosts-axis
                # size the two-level psum closed over, and the registered
                # population census the window's cohort was sampled from.
                capacity = int(self.padded_nodes)
                mesh_nodes = mesh_axis_size(self.mesh)
                mesh_hosts = mesh_axis_size(self.mesh, HOST_AXIS)
                pop_size = (
                    0 if self.population is None else int(self.population.registered)
                )
                fn = self._wrapped_program(
                    kind, epochs, n_rounds, w.ndim, donate, tele_on, a_ndim,
                    codec, frac, model_axes, mesh_layout, fedbuff, stale_exp,
                    capacity, mesh_nodes, mesh_hosts, pop_size,
                )
                fresh, self._fresh_program = self._fresh_program, None
                if Settings.TRACE_CONTRACTS:
                    # Dispatch-time contract: the fetched program's build-time
                    # stamp must match THIS dispatch's resolved knob values.
                    concurrency.check_contract(
                        fn,
                        {
                            "ENGINE_TELEMETRY": bool(tele_on),
                            "ENGINE_WIRE_CODEC": int(codec),
                            "WIRE_TOPK_FRAC": float(frac),
                            "ENGINE_DONATE": bool(donate),
                            "SHARD_MODEL": int(model_axes),
                            "SHARD_LAYOUT": str(mesh_layout),
                            "ASYNC_STALENESS_EXP": float(stale_exp),
                            "SHARD_HOSTS": int(mesh_hosts),
                            "POPULATION_CLIENTS": int(pop_size),
                        },
                    )
                if Settings.RANK_CONTRACTS:
                    # Dispatch receipt: append this program's (cache key,
                    # lowered-HLO fingerprint) digest to the per-process
                    # ordered log — crosshost.launch compares the sequences
                    # across ranks (tpfl.parallel.ranksafe, the rank pass's
                    # runtime half).
                    receipt_key = (
                        kind, int(epochs), int(n_rounds), int(w.ndim),
                        bool(donate), bool(tele_on), int(a_ndim), int(codec),
                        float(frac), int(model_axes), str(mesh_layout),
                        bool(fedbuff), float(stale_exp), int(capacity),
                        int(mesh_nodes), int(mesh_hosts), int(pop_size),
                    )
                    ranksafe.record_dispatch(
                        receipt_key, self._hlo_digest(receipt_key, args)
                    )

            prof = profiling.rounds.enabled()
            node_tag = f"engine:{profiling.module_tag(self.module)}"
            if prof:
                self._windows += 1
                profiling.rounds.begin_round(node_tag, self._windows)
            timed = prof or tele_on or fresh is not None
            t0 = time.monotonic() if timed else 0.0
            # A new program is traced and compiled inside its first
            # call: a recompile shows as one long program_call, and as
            # a first_call row of the set-up account.
            with tracing.engine_span("program_call", window_start):
                try:
                    out = fn(*args)
                except Exception as e:
                    self._dump_flight(e, kind, n_rounds)
                    raise
                tele = None
                if tele_on:
                    out_params, out_c, out_cg, out_aux, losses, tele = out
                    # Start the carry's device→host copy NOW, non-blocking:
                    # it lands while the device (and the host) move on, so
                    # finalize's np.asarray reads host memory instead of
                    # stalling the dispatch pipeline.
                    start_host_copy(tele)
                else:
                    out_params, out_c, out_cg, out_aux, losses = out
            self._rounds_done += n_rounds
            t1 = time.monotonic() if timed else 0.0
            if fresh is not None:
                profiling.observatory.first_call(fresh, t0, t1)
            return EngineWindow(
                self, kind, aux is not None,
                (out_params, out_c, out_cg, out_aux, losses), tele, w,
                n_rounds, window_start, self._windows, prof, node_tag,
                t0, t1,
            )

    def _account_init(self, t_init: float) -> None:
        """The tail of ``__init__`` (kept down here so that the line
        numbers of the round body above, which the Pallas kernels'
        serialized bodies carry, move only with the round body): arm
        the persistent compilation cache where the knob asks, and row
        the constructor in the set-up account — what this process
        traces, lowers, loads and compiles from the constructor's first
        line on is in it."""
        if Settings.COMPILE_CACHE_DIR:
            # COMPILE_CACHE_DIR, unless JAX_COMPILATION_CACHE_DIR
            # already placed it (profiling.compile_cache_dir): warm
            # processes reload lowered executables instead of
            # recompiling; tpfl_compile_cache_warm_total counts them.
            profiling.ensure_compile_cache(str(Settings.COMPILE_CACHE_DIR))
        profiling.observatory.first_call(
            "engine_init", t_init, time.monotonic()
        )

    def _hlo_digest(self, key: tuple, args: tuple) -> str:
        """Lowered-HLO fingerprint of the cached program behind
        ``key``, traced lazily once per cache key (RANK_CONTRACTS
        only): two ranks agreeing on the key but lowering different
        bytes — layout drift, version skew — must still diverge in the
        receipt. Lowering re-traces without executing, so donated
        inputs are untouched; any backend that cannot lower here
        degrades to a key-only digest rather than failing dispatch."""
        fp = self._hlo_digests.get(key)
        if fp is None:
            try:
                fp = ranksafe.hlo_fingerprint(
                    self._programs[key].lower(*args).as_text()
                )
            except Exception:
                fp = ""
            self._hlo_digests[key] = fp
        return fp

    def _dump_flight(self, exc: Exception, kind: str, n_rounds: int) -> None:
        """Black-box the failed dispatch: an ``engine_failure`` event
        in the ``engine`` flight ring, then the ring dumped as
        ``flight-engine-<reason>.json`` (when TELEMETRY_DUMP_DIR is
        set) — the same post-mortem discipline as ``Node.stop`` and
        the chaos harness's crash paths."""
        try:
            from tpfl.management.telemetry import flight

            flight.record(
                "engine",
                {
                    "kind": "event",
                    "name": "engine_failure",
                    "node": "engine",
                    "trace": "",
                    "t": time.monotonic(),
                    "model": profiling.module_tag(self.module),
                    "program": f"{kind}x{n_rounds}",
                    "error": f"{type(exc).__name__}: {exc}"[:200],
                },
            )
            flight.dump("engine", type(exc).__name__.lower())
        except Exception:
            pass  # observability must never mask the real failure

    # --- evaluation ------------------------------------------------------

    def _build_eval(self, with_aux: bool) -> Callable:
        module = self.module
        loss_fn = self._loss_fn

        @jax.jit
        def eval_fn(params, aux, xs, ys):
            def one_node(p, a, xb, yb):
                def one_batch(carry, batch):
                    x, y = batch
                    logits = module.apply({"params": p, **a}, x, train=False)
                    loss = loss_fn(logits, y).mean()
                    acc = jnp.mean(jnp.argmax(logits, -1) == y)
                    return carry, (loss, acc)

                _, (losses, accs) = lax.scan(one_batch, 0.0, (xb, yb))
                return jnp.mean(losses), jnp.mean(accs)

            return jax.vmap(one_node)(params, aux, xs, ys)

        if with_aux:
            return eval_fn
        return jax.jit(lambda params, xs, ys: eval_fn(params, {}, xs, ys))

    def evaluate(
        self, params: Any, xs: Any, ys: Any, aux: Optional[Any] = None
    ) -> tuple[Any, Any]:
        """Per-node (loss, accuracy) over node-stacked eval data."""
        with_aux = aux is not None
        fn = self._eval_fns.get(with_aux)
        if fn is None:
            fn = self._eval_fns[with_aux] = self._build_eval(with_aux)
        if with_aux:
            return fn(
                self.pad_stacked(params), self.pad_stacked(aux),
                self.pad_stacked(xs), self.pad_stacked(ys),
            )
        return fn(
            self.pad_stacked(params), self.pad_stacked(xs),
            self.pad_stacked(ys),
        )


# --- buffer-donation inspection ------------------------------------------


def donation_analysis(
    jitted_fn: Callable,
    args: tuple,
    donate_argnums: tuple[int, ...] = (0, 1, 2, 3),
) -> dict:
    """Inspect a jitted program's buffer donation through BOTH compiler
    stages: the JAX lowering (every donated leaf must carry a
    ``tf.aliasing_output`` marker — a ``jax.buffer_donor`` marker means
    JAX accepted the donation but found no aliasable output, i.e. the
    buffer is freed, not reused) and the compiled HLO's
    ``input_output_alias`` table (the executable actually writes
    outputs into the donated input buffers). Returns::

        {"donated_leaves": int,   # array leaves under donate_argnums
         "aliased": int,          # tf.aliasing_output markers
         "unaliased_donors": int, # jax.buffer_donor markers
         "output_aliases": int,   # compiled input_output_alias pairs
         "clean": bool}           # all three columns agree

    ``clean`` is the CI gate: a donating round program that stages a
    copy (or silently drops a donation) regresses it."""
    donated_leaves = len(
        jax.tree_util.tree_leaves(tuple(args[i] for i in donate_argnums))
    )
    low = jitted_fn.lower(*args)
    txt = low.as_text()
    aliased = txt.count("tf.aliasing_output")
    donors = txt.count("jax.buffer_donor")
    header = low.compile().as_text().splitlines()[0]
    m = re.search(r"input_output_alias=\{(.*?)\s\}", header)
    output_aliases = len(re.findall(r"\(\d+,", m.group(1))) if m else 0
    return {
        "donated_leaves": donated_leaves,
        "aliased": aliased,
        "unaliased_donors": donors,
        "output_aliases": output_aliases,
        "clean": bool(
            donors == 0
            and aliased == donated_leaves
            and output_aliases == donated_leaves
        ),
    }


# --- batched-fit programs (the pool's side of the seam) ------------------


def build_masked_local_fit(
    module: Any,
    opt: Any,
    loss_fn: Callable,
    has_aux: bool,
    track_grads: bool,
    epochs: int,
) -> Callable:
    """One node's masked local fit for the batched pool: epochs x scan
    over batches through :func:`make_train_step` (THE local SGD step —
    identical numerics to ``JaxLearner.fit``), with per-batch 0/1
    masks turning padding batches into exact no-ops and optional raw-
    gradient accumulation (SCAFFOLD's control variates)."""
    step = make_train_step(module, loss_fn, has_aux, with_grads=track_grads)

    def local_fit(params, aux, correction, anchor, mu, xs, ys, bmask):
        state = TrainState.create(
            apply_fn=None, params=params, tx=opt, aux_state=aux
        )
        gsum0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(
                p.shape, jnp.promote_types(p.dtype, jnp.float32)
            ),
            state.params,
        ) if track_grads else jnp.float32(0)

        def batch_step(carry, batch):
            st, gsum = carry
            x, y, m = batch
            if track_grads:
                st2, (loss, _acc, g) = step(st, x, y, correction, anchor, mu)
                # Padding batches (m == 0) contribute zero gradient.
                gsum = jax.tree_util.tree_map(
                    lambda a, gg: a + (gg * m).astype(a.dtype), gsum, g
                )
            else:
                st2, (loss, _acc) = step(st, x, y, correction, anchor, mu)
            # Masked (padding) batches are exact no-ops.
            keep = m > 0
            st = jax.tree_util.tree_map(
                lambda old, new: jnp.where(keep, new, old), st, st2
            )
            return (st, gsum), loss * m

        def epoch_step(carry, _):
            carry, losses = lax.scan(batch_step, carry, (xs, ys, bmask))
            return carry, jnp.sum(losses) / jnp.maximum(jnp.sum(bmask), 1.0)

        (state, gsum), epoch_losses = lax.scan(
            epoch_step, (state, gsum0), None, length=epochs
        )
        return state.params, state.aux_state, epoch_losses[-1], gsum

    return local_fit


def build_batched_fit_program(
    module: Any,
    opt: Any,
    loss_fn: Callable,
    has_aux: bool,
    track_grads: bool,
    epochs: int,
) -> Callable:
    """The pool's compiled ``vmap(local_fit)`` over the stacked node
    axis. The jit carries no explicit shardings: inputs placed by
    :func:`maybe_nodes_mesh` + ``federation_sharding`` run sharded
    (SPMD over the node axis), host-resident inputs run single-device
    — one program either way."""
    local_fit = build_masked_local_fit(
        module, opt, loss_fn, has_aux, track_grads, epochs
    )
    return jax.jit(jax.vmap(local_fit), donate_argnums=(0, 1))
