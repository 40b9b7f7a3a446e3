"""Global configuration.

Mirrors the surface of the reference's ``p2pfl/settings.py`` (class-level
constants, mutable at runtime before nodes start) while adding the
profile system the reference scatters across ``utils/utils.py:39`` and
``examples/mnist.py:43``.  Reference: ``p2pfl/settings.py:28-153``.

Values are read at use-time (not captured at import) everywhere in tpfl,
so mutating ``Settings.X`` between experiments is safe — this fixes the
import-capture footgun noted in the reference (``examples/mnist.py:262``).
"""

from __future__ import annotations

import os
from typing import Any


class Settings:
    """Class-level configuration constants, mutable before node start."""

    # --- gRPC / transport ---
    GRPC_TIMEOUT: float = 10.0
    """Timeout (s) for unary RPCs (handshake/disconnect/send)."""

    MAX_MESSAGE_SIZE: int = 1024 * 1024 * 1024
    """Max gRPC message size (1 GiB) — parity with grpc_server.py:65."""

    ELECTION: str = "vote"
    """Train-set election mode. "vote" (default): the reference's
    random-weight vote — every node floods a vote and tallies
    (vote_train_set_stage.py:79-171); O(N²) messages per round plus a
    VOTE_TIMEOUT wait whenever any vote is missing. "hash":
    deterministic sortition — rank candidates by
    H(exp_name, round, addr) and take the top TRAIN_SET_SIZE; zero
    messages, zero wait, and all nodes agree whenever their membership
    views agree (digest heartbeats give full view before learning
    starts). The per-round set still rotates pseudo-randomly with the
    round number. Recommended for 100+ node federations.

    Adversarial model: the rank mixes in a per-experiment random
    beacon (hash of the initiator's init-model bytes, carried by the
    StartLearning broadcast — stages.base_node.election_rank), so an
    address committed BEFORE the experiment starts cannot be ground to
    rank top-K: the beacon is unknown at address-choice time, and a
    fixed address's election frequency under random beacons is uniform
    (tested). What remains is a pre-commitment assumption: an
    adversary that observes the beacon and only THEN joins with a
    freshly ground address still wins, and the initiator itself could
    grind init weights to favor an address it controls. Deployments
    that cannot pre-commit membership (or trust the initiator) should
    keep "vote" (each elector samples with private randomness) — the
    global default — and pair hash election with a robust aggregator
    (tpfl.learning.aggregators.robust) when they need both scale and
    poisoning tolerance. See docs/protocol.md."""

    INIT_GOSSIP_STATIC_EXIT_S: float = 30.0
    """Wall-clock quiet window before the init-weights diffusion stops
    pushing to silent neighbors (StartLearningStage). Iteration-count
    exits proved too aggressive at 500-node scale, where the
    StartLearning flood itself takes tens of seconds to spread."""

    GRPC_SERVER_WORKERS: int = 16
    """gRPC server handler threads. The reference pins 2
    (grpc_server.py:67); a multislice host fanning out to tens of peers
    serializes handler work at that width — raise for dense hubs."""

    # --- transport resilience (retry / circuit breaker) ---
    RETRY_MAX_ATTEMPTS: int = 3
    """Total attempts per outbound send (unary and streamed): 1 = the
    reference's fire-once behavior. Retries are safe — control messages
    dedup by hash at the receiver, weight payloads by round/contributor
    bookkeeping — so a duplicate delivery from a retried send that
    actually arrived is absorbed."""

    RETRY_BASE_DELAY: float = 0.05
    """Backoff before retry k is ``min(RETRY_MAX_DELAY,
    RETRY_BASE_DELAY * 2**k)`` scaled by equal jitter in [0.5, 1.5)
    drawn from a per-node seeded RNG (deterministic under
    Settings.SEED)."""

    RETRY_MAX_DELAY: float = 2.0
    """Cap on a single backoff sleep (seconds)."""

    BREAKER_THRESHOLD: int = 3
    """Consecutive *failed sends* (each already retried
    RETRY_MAX_ATTEMPTS times) to a neighbor before its circuit opens:
    the peer is marked suspect, evicted from the table, and no longer
    costs send budget. The reference evicts on the FIRST failed send
    (grpc_client.py:176-183), which a single lost packet can trigger."""

    BREAKER_PROBE_PERIOD: float = 10.0
    """Seconds between half-open reconnect probes to a suspect peer
    (rides the heartbeater cadence, so the effective period is
    ``max(BREAKER_PROBE_PERIOD, HEARTBEAT_PERIOD)``). A successful
    probe handshake — or an incoming beat from the peer — closes the
    circuit and re-admits it."""

    # --- logging ---
    LOG_LEVEL: str = "INFO"
    FILE_LOGGER: bool = True
    LOG_DIR: str = "logs"
    LOG_FILE_MAX_BYTES: int = 10_000_000
    LOG_FILE_BACKUP_COUNT: int = 3
    ASYNC_LOGGER: bool = True

    # --- simulation ---
    DISABLE_SIMULATION: bool = False
    """When True, learners run inline instead of through the batching
    pool (tpfl.simulation.SuperLearnerPool)."""

    SIM_WORKERS: int = 0
    """Threads for the pool's non-batchable fallback fits; 0 = cpu_count."""

    SIM_BATCH_WINDOW: float = 0.2
    """Seconds the pool waits after the first fit submission for the
    rest of the train set to arrive before dispatching the batch."""

    SIM_BATCH_MAX_WAIT: float = 5.0
    """Upper bound on holding a hinted fit group open (a straggler
    later than this trains in its own dispatch)."""

    SIM_MAX_BATCH_NODES: int = 128
    """Chunk size for the vmapped batched fit (memory bound: params ×
    chunk nodes resident). SURVEY 'hard parts': 1000-node sim."""

    SIM_PROCESS_ISOLATION: bool = False
    """When True, the pool's fallback fits run in spawned worker
    processes (tpfl.simulation.isolated): a crashing learner / native
    segfault kills one worker, not the whole federation — the
    reference's Ray-actor isolation property (actor_pool.py:203-357),
    opt-in because process round-trips cost what the thread pool
    avoids. Scope: plain JaxLearner fits (no callbacks/aux); other
    jobs stay on the thread pool."""

    # --- heartbeat ---
    HEARTBEAT_PERIOD: float = 2.0
    HEARTBEAT_TIMEOUT: float = 5.0

    # --- gossip (control plane) ---
    GOSSIP_PERIOD: float = 0.1
    TTL: int = 10
    GOSSIP_MESSAGES_PER_PERIOD: int = 100
    AMOUNT_LAST_MESSAGES_SAVED: int = 100

    # --- gossip (model data plane) ---
    GOSSIP_MODELS_PERIOD: float = 1.0
    GOSSIP_MODELS_PER_ROUND: int = 2
    GOSSIP_EXIT_ON_X_EQUAL_ROUNDS: int = 10
    # Downcast float parameters on the wire ("bfloat16"/"float16"; None
    # = exact). Halves model-gossip bytes over DCN; receivers restore
    # their model's own dtype on set. Lossy (~3 decimal digits for
    # bf16) — FedAvg tolerates it, leave None for exact-repro runs.
    # Applies to the DENSE codec only; WIRE_CODEC supersedes it.
    WIRE_DTYPE: str | None = None

    # --- wire codec (model payload compression) ---
    WIRE_CODEC: str = "dense"
    """Model-payload wire codec (tpfl.learning.compression): "dense"
    (v1 envelope, exact, what old peers decode), or a '+'-composed
    stack of "quant8" (int8 symmetric per-leaf quantization, jitted),
    "topk" (top-k magnitude sparsification, index+value packing) and
    one entropy coder ("zlib", or "zstd" when the optional zstandard
    package is installed). E.g. "quant8+zlib". Validated at use time —
    unknown names raise ValueError. Lossy codecs are within FedAvg /
    SCAFFOLD convergence noise on the digits path at ≥4x fewer payload
    bytes (``tests/test_compression.py::test_wire_ab_codec_moves_4x_fewer_bytes_at_loss_parity``)."""

    WIRE_TOPK_FRAC: float = 0.05
    """Fraction of entries per leaf the "topk" codec keeps (by
    magnitude). Only read when WIRE_CODEC includes "topk"."""

    WIRE_ENTROPY_LEVEL: int = 1
    """Compression level for the entropy stage (zlib/zstd). 1 favors
    encode throughput — the gossip hot path encodes once per model
    version but at a 1000-node hub every CPU cycle is contended."""

    WIRE_DELTA: bool = False
    """Residual (delta) gossip: once a round's aggregate is adopted it
    becomes a BASE (tpfl.learning.compression.BaseCache); the next
    round's full-model pushes to peers that acknowledged that base
    (nei_status == round-1) carry only ``current - base``, which
    quantizes/compresses far smaller than the full weights. A peer
    without the base nacks (``codec_nack``) and the sender falls back
    to dense for it — old peers and fresh joiners keep working."""

    WIRE_CHUNK_SIZE: int = 256 * 1024
    """gRPC payload chunking threshold AND chunk size (bytes). Messages
    larger than this stream as CRC-tagged chunks over a dedicated
    streaming RPC instead of one multi-MB unary frame, so heartbeats
    and votes no longer queue behind a model transfer on the wire
    (head-of-line). 0 disables chunking."""

    # --- zero-copy model plane ---
    WIRE_FORMAT: int = 3
    """Dense model-payload envelope version. 3 (default): the zero-copy
    layout — msgpack header (dtype/shape/offset table) + ONE contiguous
    payload staged through the node's BufferPool; encode writes each
    leaf's bytes exactly once, decode returns read-only memoryview-
    backed array views with zero per-leaf copies. 1: the legacy dense
    msgpack map, for federations that still contain pre-v3 peers (every
    tpfl node decodes v1, v2 AND v3 regardless of this setting — it
    only selects what WE emit). Compressed codecs (WIRE_CODEC) emit v2
    envelopes independently of this knob."""

    INPROC_ZERO_COPY: bool = False
    """In-memory transport fast path: hand model payloads between
    co-located nodes BY REFERENCE (tpfl.learning.serialization
    .InprocModelRef) — no encode, no decode, no bytes at all. Leaves
    are frozen (read-only numpy views; jax arrays are immutable) and
    contributor metadata is copied, so neither side can mutate the
    other (tests/test_zero_copy.py asserts non-aliasing under both
    settings). gRPC federations are unaffected: the flag only takes
    effect on transports that declare ZERO_COPY_INPROC, and the wire
    bytes of every gRPC payload stay identical either way. Off by
    default for reference parity; the scale profile enables it — at
    1000 single-host nodes the encode/decode of every gossip push was
    memcpy the receiver shares an address space with."""

    BUFFER_POOL_BUFFERS: int = 8
    """Max reusable serialization buffers a BufferPool retains
    (tpfl.learning.bufferpool). The steady state is one buffer per
    node, reused every encode; extras cover concurrent encode paths
    (gossiper + relay + init diffusion)."""

    BUFFER_POOL_MAX_BYTES: int = 256 * 1024 * 1024
    """Cap on the total bytes a BufferPool may keep pooled. Returned
    buffers that would exceed it are freed instead of pooled."""

    # --- SSL / mTLS ---
    USE_SSL: bool = False
    CA_CRT: str = ""
    SERVER_CRT: str = ""
    SERVER_KEY: str = ""
    CLIENT_CRT: str = ""
    CLIENT_KEY: str = ""

    # --- FL round protocol ---
    TRAIN_SET_SIZE: int = 4
    VOTE_TIMEOUT: float = 60.0
    AGGREGATION_TIMEOUT: float = 300.0
    WAIT_HEARTBEATS_CONVERGENCE: float = 0.2

    # --- asynchronous buffered rounds (FedBuff-style) ---
    ASYNC_ROUNDS: bool = False
    """Master gate for the asynchronous round lifecycle
    (stages.base_node.AsyncRoundStage): every live peer trains
    continuously and contributes whenever its fit finishes — no vote
    election and no slowest-trainer barrier. Each node's aggregator
    folds arrivals as a buffered FedBuff-style round
    (``Aggregator.set_nodes_to_aggregate(async_k=...)``): a
    contribution trained from model-version ordinal ``v`` folding into
    round ``r`` carries staleness ``τ = r - v`` and weight
    ``num_samples / (1 + τ)**ASYNC_STALENESS_EXP``; the round closes on
    buffer-full (``ASYNC_BUFFER_K`` distinct contributors) or the
    ``ASYNC_ROUND_DEADLINE`` failsafe — a dead trainer costs nothing
    instead of AGGREGATION_TIMEOUT (the quorum-degradation economics,
    without the barrier that made them necessary). Off (default):
    the synchronous vote/train/wait lifecycle, reference parity.
    See docs/protocol.md "Asynchronous buffered rounds"."""

    ASYNC_BUFFER_K: int = 4
    """Contributions (distinct contributors) that close an async
    round's buffer — FedBuff's K. Clamped per round to the live peer
    count; 1 is the degenerate fully-sequential buffer (every single
    contribution makes a round)."""

    ASYNC_STALENESS_EXP: float = 0.5
    """Staleness-decay exponent: a contribution ``τ`` versions stale
    folds at weight ``w(τ) = 1/(1+τ)**exp`` times its sample count.
    0 disables staleness discounting (pure buffered FedAvg); 0.5 is
    FedBuff's ``1/sqrt(1+τ)``; larger values silence stragglers
    faster."""

    ASYNC_ROUND_DEADLINE: float = 30.0
    """Failsafe (s) on an async round staying open short of
    ASYNC_BUFFER_K contributions: at the deadline the round closes
    with whatever the buffer holds (``round_deadline`` flight event +
    ``tpfl_agg_deadline_total``). An EMPTY buffer at the deadline
    fails open loudly — the round stays open (there is nothing to
    aggregate) and the stage re-arms the deadline."""

    ASYNC_SERIALIZED: bool = True
    """Deterministic async discipline (test/standalone profiles):
    arrivals buffer without folding and the round-close fold runs in a
    serialized deterministic order — schedule order when a seeded
    :class:`tpfl.communication.faults.AsyncSchedule` is attached to
    the aggregator (the reorder-buffer admission that makes same-seed
    runs byte-identical, ``tests/test_async_control.py``), else canonical
    (contributor-sorted) order. False (scale profile): free-running —
    contributions fold eagerly in arrival order (AGG_STREAM_EAGER
    semantics), maximum throughput, no reproducibility guarantee."""

    ASYNC_ADAPTIVE: bool = False
    """Adaptive async control plane (tpfl.learning.async_control
    .AsyncController): when on, each node tunes its EFFECTIVE buffer K
    and round deadline per round from the observed inter-arrival and
    staleness distributions (EWMA over per-round order-invariant
    summaries + the ASYNC_CTL_QUANTILE inter-arrival quantile), bounded
    by [ASYNC_K_MIN, ASYNC_K_MAX] and (0, ASYNC_ROUND_DEADLINE].
    ASYNC_BUFFER_K / ASYNC_ROUND_DEADLINE become the starting point and
    the deadline ceiling instead of static values. In serialized mode
    the controller's observations derive from the seeded AsyncSchedule
    VIRTUAL clock (arrival ordinals without one), so same-seed runs
    keep byte-identical K/deadline trajectories at every node; free-
    running observations use the monotonic clock. Off (default): the
    PR-10 static knobs, bit-identical behavior."""

    ASYNC_K_MIN: int = 2
    """Lower bound on the adaptive controller's effective buffer K
    (ASYNC_ADAPTIVE). K=1 degenerates to a fully-sequential buffer
    where any single flooder makes a round — 2 keeps at least one
    honest arrival in every defended round's fold."""

    ASYNC_K_MAX: int = 16
    """Upper bound on the adaptive controller's effective buffer K
    (further clamped per round to the live fleet size). A K at the
    fleet size is the synchronous barrier again — the controller grows
    toward this only while buffers fill fast and staleness stays low."""

    ASYNC_CTL_EWMA: float = 0.3
    """EWMA smoothing factor for the controller's per-round observation
    summaries (inter-arrival quantile, mean staleness, fill time):
    ``s <- (1-a)*s + a*x``. Higher = reacts faster to fleet changes,
    lower = steadier knobs. Only read when ASYNC_ADAPTIVE."""

    ASYNC_CTL_QUANTILE: float = 0.9
    """Inter-arrival quantile the controller's deadline targets: the
    effective deadline covers ``K`` arrivals at this quantile of the
    observed inter-arrival distribution (x a fixed 4x safety margin),
    clamped to ASYNC_ROUND_DEADLINE. 0.9 tolerates a 10% arrival tail
    without deadline-closing the round. Only read when ASYNC_ADAPTIVE."""

    ASYNC_STALENESS_MAX: int = 16
    """Staleness plausibility bound, two consumers: (1) the robust
    aggregators (Krum/MultiKrum/TrimmedMean) REJECT buffered candidates
    whose ``τ`` exceeds it at finalize (boundary τ == max is kept;
    all-rejected fails open loudly — a defense never bricks a round);
    (2) the anomaly scorer flags contributions past it — or whose
    version ordinal REGRESSES below one the same peer already
    contributed — as ``stale_flood``, the buffer-stuffing attack
    signature (tpfl.attacks.plan: stale_flood / withhold_replay), which
    the quarantine engine then excludes like any other anomaly class.
    Negative disables both. Honest stragglers sit at single-digit τ in
    every measured configuration; 16 is far past the staleness-weight
    floor (w(16) ≈ 0.24 at the default exp) where a contribution stops
    mattering anyway."""

    ASYNC_UNTAGGED_POLICY: str = "fresh"
    """Freshness semantics for UNTAGGED contributions
    (``Message.version == -1``: pre-async peers, or a spoofing
    adversary omitting the tag to bypass staleness weighting):
    "fresh" — τ=0, full weight (reference-parity default: a pre-async
    peer is not penalized); "max-stale" — τ = ASYNC_STALENESS_MAX, the
    most-discounted weight that still folds (the scale default:
    untagged mass cannot dominate a buffer); "reject" — refused at
    intake with ``tpfl_agg_untagged_rejected_total`` (strict
    deployments where every peer is known to tag). The policy applies
    to the staleness weight, the robust candidates' τ, and the
    quarantine/ledger window the same way — one resolved τ per
    contribution. Sync rounds ignore it (every sync contribution is
    τ=0 by construction)."""

    # --- aggregation (streaming accumulators) ---
    AGG_STREAM_EAGER: bool = True
    """Fold contributions into the aggregator's on-device running
    accumulator AS THEY ARRIVE (Aggregator.accumulate/finalize) instead
    of reducing everything at round close. Peak memory for mean-style
    aggregators (FedAvg/FedProx/SCAFFOLD) is O(1 model) either way —
    the batch path also folds sequentially with buffer donation — but
    the eager path moves the reduce off the round's critical tail: by
    the time coverage completes, the aggregate is one finalize away.
    Trade-off: the fold runs in ARRIVAL order, so bit-exact
    run-to-run reproducibility of the aggregate (float addition is not
    associative) requires False, which folds the held models in
    canonical sorted order at close instead. The test and standalone
    profiles set False (exactness/reference parity first); the scale
    profile sets True."""

    AGG_MEDIAN_RESERVOIR: int = 64
    """FedMedian's streaming state keeps at most this many contributions
    (seeded reservoir sampling beyond it) — an exact median up to the
    cap, an unbiased sampled median past it, and bounded memory at any
    federation size."""

    ROUND_QUORUM: float = 1.0
    """Fraction of the *live* train set whose contributions close a
    round's aggregation. 1.0 (default) = reference behavior: every
    expected contributor must report (or the deadline/stall fires).
    When heartbeat loss evicts a train-set member mid-round the
    expected set shrinks to the live members
    (Aggregator.remove_dead_nodes), so a crashed trainer no longer
    costs every peer the full AGGREGATION_TIMEOUT; ROUND_QUORUM < 1.0
    additionally lets aggregation close before slow-but-alive members
    report — use with care: unlike AGGREGATION_STALL it does not wait
    for intake to go quiet, so an aggressive quorum can fracture the
    aggregate mid-exchange exactly like an undersized stall window."""

    # --- observability ---
    RESOURCE_MONITOR_PERIOD: float = 1.0

    TELEMETRY_ENABLED: bool = False
    """Master gate for hop-level distributed tracing
    (tpfl.management.tracing): when on, every model-payload encode
    mints a 16-byte trace id that rides the wire envelope (v3 header
    ``tid`` extension; v1/v2 peers still decode) and the in-proc
    ``InprocModelRef``, and every gossip hop, retry, breaker trip,
    decode, and aggregation fold becomes a span in the per-node flight
    recorder — reconstructable across nodes into a round timeline by
    ``tools/traceview.py``. Off by default: the metrics REGISTRY
    (``logger.metrics``) always records (cheap per-thread dict
    updates), but span minting/recording is gated here — off, a span
    site records nothing
    (``tests/test_telemetry.py::test_span_gating_and_ring_bound``); the
    cost when on is not measured on the chip. Read at use time, so it
    can be toggled between experiments."""

    TELEMETRY_RING: int = 512
    """Flight-recorder capacity: the last N spans/events retained PER
    NODE (tpfl.management.telemetry.FlightRecorder). The ring is what
    ``Node.stop()`` and the chaos harness dump on crash or quorum
    degradation — size it to cover at least one full round of spans
    for post-mortems (a 4-node round is a few hundred spans)."""

    TELEMETRY_MAX_LABELSETS: int = 64
    """Label-cardinality cap per metric in the registry
    (tpfl.management.telemetry.MetricsRegistry): label sets beyond the
    cap collapse into a reserved ``{"overflow": "true"}`` series
    instead of growing without bound — a per-peer label on a
    1000-node federation must not turn the registry into the leak it
    exists to observe."""

    TELEMETRY_DUMP_DIR: str = ""
    """Directory for flight-recorder crash dumps (JSON, one file per
    (node, reason)). Empty (default) disables file dumps — the ring
    still records and ``logger.metrics``/``FlightRecorder.snapshot``
    stay queryable in-process. Set by the chaos harness so
    every injected crash and quorum degradation is post-mortem-able."""

    METRIC_MAX_POINTS: int = 4096
    """Per-series point cap in the local/global metric stores
    (tpfl.management.metric_storage): a series keeps the most recent N
    (step, value) / (round, value) points, evicting oldest-first. An
    unbounded per-step series on a long-running node was the only
    unbounded memory left in the management layer."""

    FLEETOBS_SNAPSHOT_PERIOD: float = 0.0
    """Cadence (s) of the fleet-observatory snapshot publisher
    (tpfl.management.fleetobs.FleetPublisher): every period the
    process' MetricsRegistry is folded and written atomically as
    ``fleetsnap-<origin>.json`` into ``FLEETOBS_DIR``, where rank 0
    (or any scraper) folds all ranks' snapshots into ONE fleet
    registry (``MetricsRegistry.merge`` semantics, ``origin=<rank>``
    labels) served by ``MetricsHTTPServer`` ``/fleet.json``. 0.0
    (default) = no publisher thread; the crosshost receipt path still
    embeds a one-shot snapshot per worker regardless (that path is
    pull-per-run, not periodic)."""

    FLEETOBS_DIR: str = ""
    """Directory the fleet snapshot publisher writes to and the fleet
    fold reads from (one ``fleetsnap-<origin>.json`` per process,
    written tmp+rename so readers never see a torn document). Empty
    (default) disables file publishing even when
    ``FLEETOBS_SNAPSHOT_PERIOD`` is set — multi-host deployments point
    every rank at one shared path (NFS/GCS-fuse), single-host
    simulations at any tmp dir."""

    SLO_TARGETS: str = ""
    """Declared service-level objectives the live watchdog
    (tpfl.management.fleetobs.SLOWatchdog) evaluates over the metrics
    registry: semicolon-separated clauses ``expr op value`` with
    ``expr`` one of ``rate(counter)`` (per-second rate between
    evaluations), ``gauge(name)`` (latest value, summed across label
    sets), ``ratio(a, b)`` (counter ``a`` per counter ``b`` —
    e.g. DCN bytes per engine round) and ``op`` one of ``< <= > >=``.
    Example: ``"rate(tpfl_engine_rounds_total) >= 2.0;
    gauge(tpfl_engine_loss) <= 2.5"``. Signals are
    EWMA-smoothed (``SLO_EWMA``); ``SLO_BREACH_WINDOWS`` consecutive
    violating evaluations emit a ``slo_breach`` flight event and bump
    ``tpfl_slo_breach_total`` — a regression gate inside running
    federations. Empty (default) = watchdog idle."""

    SLO_EWMA: float = 0.3
    """EWMA smoothing factor for SLO watchdog signals (weight of the
    NEWEST observation; 1.0 = no smoothing). Smoothing keeps a single
    slow scrape interval or GC pause from counting as a breach window
    — the watchdog is after sustained regressions, not blips."""

    SLO_BREACH_WINDOWS: int = 2
    """Consecutive violating evaluations before a breach fires (the
    ``slo_breach`` flight event + ``tpfl_slo_breach_total`` counter).
    The streak resets on any healthy evaluation; after firing, the
    breach re-arms only once the target goes healthy again — a
    sustained breach is ONE event, not one per evaluation."""

    GOSSIP_METRICS: bool = True
    """Broadcast eval metrics to the federation after each round
    (reference MetricsCommand behavior). At N nodes each broadcast
    TTL-floods through every node — O(N²) handler work per round for
    observability only — so the scale profile turns it off (metrics
    still log locally; the experiment result does not depend on it)."""

    AGGREGATION_STALL: float | None = None
    """When set, a trainer whose aggregation intake has gone quiet for
    this many seconds (holding at least one contribution, full
    coverage not reached) proceeds with the partial aggregate instead
    of waiting out AGGREGATION_TIMEOUT. None (default) = reference
    behavior: wait the full timeout. The scale profile sets 60.0 —
    at 1000 nodes an elected-but-unready peer otherwise costs every
    trainer the entire timeout each round (measured: the dominant
    round wall-clock term).

    Sizing: the window must comfortably exceed the worst-case
    single-payload delivery time (serialize + wire + decode + jitted
    add_model of one partial model), or the stall fires MID-EXCHANGE
    and fractures the aggregate — a 30 s stall did exactly that at
    1000 nodes (docs/deployment.md). A lossy WIRE_CODEC (e.g.
    "quant8+zlib", ~4-5x fewer payload bytes) shrinks that worst case
    proportionally, buying stall-window headroom at the same
    setting. Timed on the monotonic clock (Aggregator.stalled), so
    NTP steps cannot suppress or prematurely fire the exit."""

    ROUND_WAIT_POLL: float = 0.5
    """Upper bound (s) on the round-result wait's poll interval
    (stages._await_round_result). FullModel arrival wakes waiters
    instantly via the event; this bounds only how fast early-stop /
    local-coverage conditions are noticed. The scale profile widens it
    to 2.0 — hundreds of waiters waking 2x/s are a measurable GIL tax
    at 1000 in-process nodes."""

    # --- device-plane profiling ---
    PROFILING_ENABLED: bool = False
    """Master gate for the device-plane performance observatory
    (tpfl.management.profiling): per-call recompile detection on the
    wrapped jit seams (CompileObservatory), per-round wall-clock
    attribution spans (RoundProfiler: train/dispatch/fold/gossip/
    host_other), and the block_until_ready dispatch/compute split in
    the learner. Off by default — disabled profiling is one attribute
    read per instrumented site and adds ZERO device dispatches
    (``tests/test_profiling.py``: the disabled observatory and profiler
    record nothing); the cost when on is not measured on the chip. The
    always-cheap
    registry side (compiled-cache hit/miss counters and size gauges,
    HBM gauges) records regardless, per the PR-5 rule. Read at use
    time, so it can be toggled between experiments."""

    PROFILING_RECOMPILE_WARN: int = 8
    """Distinct abstract argument signatures (shapes/dtypes/statics)
    one wrapped program may accrete before the observatory flags a
    RECOMPILE STORM (flight-ring event + log warning). Every distinct
    signature is a fresh XLA compile — shape churn that defeats the
    jit cache is the silent killer of steady-state throughput (the
    vmap-width bucketing in simulation/batched_fit exists for exactly
    this reason). Only read when PROFILING_ENABLED."""

    PROFILING_TRACE_DIR: str = ""
    """When set, federation runs wrap the experiment (StartLearning →
    experiment finish) in a ``jax.profiler`` trace written here: the
    CLI's ``tpfl experiment run --profile DIR`` sets this via the
    ``TPFL_PROFILING_TRACE_DIR`` environment override. One process-wide
    trace at a time (in-process federations share the profiler); view
    with TensorBoard/xprof. Empty (default) disables."""

    # --- learning-plane observatory (contribution ledger) ---
    LEDGER_ENABLED: bool = False
    """Master gate for the learning-plane observatory
    (tpfl.management.ledger): per-contribution update statistics
    (L2 norm, per-leaf norm profile, cosine vs the round-start
    reference and vs the running update mean — one fused jitted
    reduction per accepted contribution, O(1) memory), the bounded
    per-node ContributionLedger ring, the ConvergenceMonitor
    (global-model delta norm + loss-trajectory slope), and the
    AnomalyScorer's sign-flip / norm-outlier detection. Off by
    default — disabled, every tap is one attribute read and adds ZERO
    device dispatches
    (``tests/test_ledger.py::test_disabled_ledger_adds_zero_dispatches``);
    the cost when on is not measured on the chip. Detection is
    observational: flags never
    change aggregation results. Read at use time."""

    LEDGER_RING: int = 1024
    """Contribution-ledger capacity: the last N contribution records
    retained PER NODE (the ring is also the anomaly scorer's
    running-baseline window, so size it to cover several rounds of
    the expected train set)."""

    LEDGER_ANOMALY_Z: float = 6.0
    """Robust z-score (vs the ledger window's median/1.4826·MAD) of a
    contribution's update L2 norm at or above which it is flagged a
    norm outlier (additive-noise signature: N(0, std) noise over d
    parameters adds std·√d of update norm — tens of sigmas at the
    attack-harness defaults, while honest updates cluster within a
    few). Only applied once LEDGER_ANOMALY_MIN_N samples exist."""

    LEDGER_ANOMALY_COS: float = 0.0
    """Cosine similarity against the round-start reference at or below
    which a contribution is flagged sign-flipped (a negated model sits
    at ≈ -1; honest contributions at ≈ +1 — the margin is wide, and
    the test needs no history, so round 0 already flags)."""

    LEDGER_ANOMALY_MIN_N: int = 4
    """Minimum single-contribution samples in the scorer's window
    before the norm-outlier z-test applies (a median/MAD over fewer
    points is noise; the cosine test is exempt — it needs no
    baseline)."""

    LEDGER_CONVERGENCE_WINDOW: int = 5
    """Trailing window (rounds/fits) for the ConvergenceMonitor's
    plateau/divergence tests and the loss-trajectory slope."""

    # --- active Byzantine defense (quarantine) ---
    QUARANTINE_ENABLED: bool = False
    """Master gate for the active defense plane
    (tpfl.management.quarantine): every single-contributor model at the
    aggregation intake is live-scored by the learning-plane ledger's
    AnomalyScorer (one fused jitted reduction, the PR-7 math) BEFORE it
    can fold — contributions flagged sign-flip / norm-outlier are
    excluded from the aggregate (kept as coverage-only passengers so
    the round still closes), the flagged peer enters quarantine, and
    subsequent clean contributions earn re-admission after
    QUARANTINE_PROBATION_ROUNDS. Requires the ledger's round state:
    enabling this activates the ledger's open-round/scoring taps even
    when LEDGER_ENABLED is off (the observational knob only gates the
    passive record path). Off by default — disabled, the intake is one
    attribute read
    (``tests/test_quarantine.py::test_disabled_defense_is_inert``); the
    cost when on is not measured on the chip. Unlike
    the ledger, quarantine is NOT observational: verdicts change what
    aggregates. Read at use time."""

    QUARANTINE_PROBATION_ROUNDS: int = 2
    """Rounds a quarantined peer's contributions must score clean
    (strictly more than this many rounds past its last flagged round)
    before it is re-admitted to the fold. Contributions during
    probation are still scored — they earn the streak — but stay
    excluded. A flagged contribution during probation re-arms the
    window from its round."""

    AGG_ROBUST_BUFFER: int = 64
    """Candidate-buffer budget for the streaming robust aggregators
    (Krum / MultiKrum / TrimmedMean): each keeps at most this many
    per-round candidates on device — a flat float32 projection matrix
    for Krum scoring, a per-leaf stacked reservoir for the trimmed
    mean — with seeded reservoir sampling past the cap (exact up to
    the cap, an unbiased sample beyond it), so peak memory is
    O(buffer), not O(contributor count)."""

    ATTACK_NOISE_STD: float = 0.1
    """Default standard deviation for the additive-noise attack when an
    AttackPlan rule does not set one (tpfl.attacks.plan; reference
    exp_SAVE3.txt:213-223 uses 0.1). Bench/test machinery, not a
    production knob."""

    # --- pod-scale federation engine (node-axis sharding) ---
    SHARD_NODES: bool = False
    """Master gate for automatic node-axis sharding in the federation
    engine (tpfl.parallel.engine): when True and more than one
    accelerator is visible, engine consumers that do not pin a mesh
    explicitly — the batched-fit pool's vmapped chunks
    (``engine.maybe_nodes_mesh``) and engines built with
    ``mesh="auto"`` — spread the stacked node axis over a ``nodes``
    mesh of the local devices, with the gossip exchange + FedAvg fold
    lowered to ``lax.psum`` collectives over ICI. Off (default): one
    device, the reference-parity layout. Determinism caveat: a FIXED
    device count is part of the reproducibility key — same seed at the
    same device count is byte-identical, but changing the device count
    regroups the fold's partial sums (docs/scaling.md)."""

    SHARD_DEVICES: int = 0
    """Cap on the devices the SHARD_NODES mesh may span: 0 (default) =
    all local devices, N > 0 = the first N. Lets a multi-tenant host
    pin the federation to a slice of the chips."""

    SHARD_MODEL: int = 1
    """Model-parallel axis size of the engine's auto mesh
    (``tpfl.parallel.engine.auto_mesh``): 1 (default) = the 1D
    ``nodes`` mesh — engine programs lower byte-identical to the
    pre-2D path; M > 1 = a 2D ``nodes x model`` mesh (``nodes`` =
    allowed devices / M, which must divide) where each node's
    parameters/optimizer state shard over the ``model`` axis per the
    ``SHARD_LAYOUT`` per-leaf PartitionSpec policy
    (``tpfl.parallel.mesh.SpecLayout``) — federate models bigger than
    one chip's HBM. The fold still reduces over ``nodes`` only; each
    model shard folds its own slice. Engines built with an explicit
    2D ``Mesh`` ignore this knob (the mesh itself carries the axis).
    Determinism: the full MESH SHAPE (nodes x model), not just the
    device count, is part of the reproducibility key — see
    docs/scaling.md."""

    SHARD_LAYOUT: str = "auto"
    """Per-leaf model-axis PartitionSpec policy for 2D meshes:
    "auto" (default) = the module's own declared layout
    (zoo ``TransformerLM.spec_layout`` = "transformer": embeddings /
    QKV / FFN sharded per ``tpfl.parallel.mesh.transformer_layout``;
    MLP/CNN/ResNet fall back to "replicated"), or a layout name from
    ``tpfl.parallel.mesh.LAYOUTS`` to force one. "replicated" keeps
    every leaf whole on each device — the model axis then only adds
    redundant compute, so force it only for parity debugging.
    Resolved at engine construction; a cache-key axis of the engine's
    round programs like the other ENGINE_* knobs."""

    SHARD_HOSTS: int = 1
    """Cross-host axis size of the engine's auto mesh
    (``tpfl.parallel.engine.auto_mesh``): 1 (default) = single-process
    meshes only — engine programs lower byte-identical to the
    single-host path; 0 = auto: one ``hosts`` slot per participating
    process (``jax.process_count()`` after
    ``tpfl.parallel.distributed.ensure_distributed``); H > 1 = a
    forced ``hosts`` axis of that size (works single-process too, for
    parity testing — the hosts axis then spans local devices). With
    hosts > 1 the engine lowers a 3D ``hosts x nodes x model`` mesh
    whose FedAvg fold decomposes into two psum legs: per-host node
    shards fold local partials over ``nodes`` (ICI), then the partial
    aggregates cross ``hosts`` over DCN — with ``ENGINE_WIRE_CODEC``
    quantizing that DCN leg natively (see docs/scaling.md "3D mesh &
    cross-host DCN"). A program-cache and ``stamp_contract`` axis like
    the other SHARD_* knobs. Read at engine construction /
    auto_mesh."""

    POPULATION_CLIENTS: int = 0
    """Registered client population of the cross-device tier
    (tpfl.parallel.population.ClientPopulation): 0 (default) = no
    population tier — every logical node is resident, the pure P2P
    layout. N > 0 = N registered, mostly-offline leaf clients attach
    to the engine's resident nodes (now edge aggregators) by per-round
    sampling: each round draws ``POPULATION_SAMPLE`` participants via
    the seeded ``sample_participants`` kernel, broadcasts the current
    edge model with ``broadcast_params``, and folds only the sampled
    cohort — so live state stays O(sampled), never O(N). Registered
    metadata (per-client round counters, last-seen) lives in a NumPy
    structure-of-arrays costing a few bytes/client. A program-cache
    and contract axis of the engine's round programs. See
    docs/scaling.md "Cross-device population tier"."""

    POPULATION_SAMPLE: int = 100
    """Participants sampled per round from the registered population
    (the K of K-out-of-N cross-device FL, pfl-research style): only
    these clients' state is materialized, trained and folded in a
    round; stragglers beyond the engine's quorum/FedBuff cutoffs are
    dropped by the same zero-weight masking as resident nodes. Read
    when a ClientPopulation is built; ignored while
    POPULATION_CLIENTS is 0."""

    SHARD_ROUNDS_PER_DISPATCH: int = 1
    """Federation rounds folded into ONE device dispatch by the
    engine's ``lax.fori_loop`` round window
    (``FederationEngine.run_rounds`` / ``FederationLearner``'s
    local-round loop). Each host dispatch costs a dispatch round
    trip (pre-PR-1 figure ~67 ms, record removed, not re-measured;
    ``chip_smoke.py``'s ``sync`` phase prints the current host's), so
    windows of K rounds pay it once per K. 1 (default) = one dispatch per round: bit-identical to the
    legacy per-round path, and interrupts (a node told to stop
    mid-fit) are honored at round granularity; larger windows are
    interruptible only between windows."""

    ENGINE_TELEMETRY: bool = False
    """Master gate for the engine plane of the observatory
    (tpfl.management.engine_obs): when on,
    ``FederationEngine.run_rounds`` compiles the TELEMETRY VARIANT of
    its round program — a fixed-shape ``[rounds, ...]`` device buffer
    threaded through the ``fori_loop`` carry that accumulates, per
    round and per node, train loss, update L2 norm, cosine vs the
    round-start reference, global-model delta norm, participation
    count and fold weight mass, all computed from values the program
    already holds (no extra HBM traffic; ``lax.psum`` only where the
    fold already psums). At window close one host-side fan-out replays
    the buffer into the existing planes: per-round ``RoundProfiler``
    rows (PROFILING_ENABLED), ``ConvergenceMonitor``
    divergence/plateau events (LEDGER_ENABLED), ``ContributionLedger``
    entries scored by the same AnomalyScorer/quarantine thresholds as
    the gRPC tier (LEDGER_ENABLED or QUARANTINE_ENABLED), and
    always-on ``tpfl_engine_*`` registry series. Off (default): the
    carry is ELIDED — the engine lowers the byte-identical round
    program of the pre-telemetry path (separate program-cache slot)
    and adds zero work. On, same-seed model outputs stay
    byte-identical at a fixed device count: telemetry is read-only
    over the carry. Read at program-build time (per run_rounds
    call). See docs/observability.md "Engine plane"."""

    ENGINE_WIRE_CODEC: str = "dense"
    """Device-side wire codec for the engine's gossip exchange
    (tpfl.parallel.engine + tpfl.learning.compression): "dense"
    (default), "quant8", "topk", or "topk+quant8". Non-dense lowers
    the PR-1 payload codec INTO the fused round program — each node's
    trained params pass the per-leaf int8-quantize→dequantize (or
    top-k mask) round-trip in-program before the fold's ``lax.psum``,
    so the exchange leg ships int8/sparse tensors over ICI/DCN
    natively (~4x fewer exchange bytes for f32 under quant8) and the
    ENGINE_TELEMETRY carry's ``wire_bytes`` row records bytes/round
    device-side (``tpfl_engine_wire_bytes``). LOSSY like the host-side
    WIRE_CODEC it mirrors (same kernels, same per-leaf policy; loss
    parity: ``tests/test_engine_wire.py::test_quantized_gossip_loss_parity``);
    "dense" compiles the byte-identical
    pre-codec program (separate program-cache slot, HLO-digest-stable
    across toggles). Entropy coders (zlib/zstd) and delta are host
    byte transforms and are rejected here at knob-read time. Read at
    program-build time (per run_rounds call); the top-k fraction
    rides ``WIRE_TOPK_FRAC``. See docs/scaling.md "Device-side wire
    codecs"."""

    ENGINE_PREFETCH: bool = False
    """Free-running engine windows in ``FederationLearner.fit``
    (tpfl.parallel.window_pipeline): when on, local rounds run through
    the :class:`~tpfl.parallel.window_pipeline.WindowPipeline` — window
    N+1 is dispatched before window N's host leg (telemetry fan-out,
    profiler rows) runs, and the next window's shuffled batch staging
    (``device_put`` placement included) happens on a named background
    prefetch thread, so dispatch RTT and host work overlap device
    compute instead of sitting between windows (the Sebulba split,
    docs/scaling.md "Free-running windows"). PERF-ONLY by
    construction: the device sees the identical program sequence over
    identical buffers, so same-seed fits are byte-identical with the
    knob on or off; interrupts stay window-granular; the prefetch
    thread is joined before fit returns. Off (default): the sequential
    window loop. Read per fit() call."""

    ENGINE_DONATE: bool = True
    """Default donation mode for the engine's round program
    (``FederationEngine.run_rounds(donate=None)``): True donates the
    state buffers (params, SCAFFOLD variates, aux) to the dispatch —
    XLA writes the fold's outputs INTO the input buffers, so a window
    costs no staging copy of the model state and peak HBM stays
    one-model-deep (verify with ``FederationEngine.donation_report``;
    ``tests/test_engine_wire.py`` pins donation-clean HLO and
    byte-identical outputs vs the non-donating variant). The handed-in
    buffers are CONSUMED — callers that re-feed the same arrays pass
    ``donate=False`` explicitly or rebind from the outputs
    (``profiling.best_of_wall_donated``).
    False: every dispatch allocates fresh outputs (debugging aid)."""

    ELASTIC_CAPACITY_MIN: int = 2
    """Floor of the elastic engine's pow-2 capacity tiers
    (tpfl.parallel.membership.MembershipView /
    tpfl.parallel.mesh.capacity_tier): the engine compiles its round
    programs at the smallest power-of-two ≥ max(live members, this
    floor), so joins/leaves/crashes/quarantine evictions inside a tier
    are pure weight-mask edits with ZERO recompiles — only crossing a
    tier boundary lowers a new program (and returning to a seen tier
    is a cache hit; the capacity is a program-cache key axis). A
    higher floor trades padded rows (wasted device work) for headroom
    before the first promotion. Read when a MembershipView is built.
    See docs/deployment.md "Elastic membership & preemption"."""

    COMPILE_CACHE_DIR: str = ""
    """Directory for JAX's persistent compilation cache, wired into
    the engine's program cache (tpfl.management.profiling
    .ensure_compile_cache, called at FederationEngine construction):
    when set, every XLA executable the engine lowers is written to
    disk, and a restarted/preempted process RELOADS it instead of
    recompiling — cold-start cost after kill-and-resume drops to cache
    I/O. The observatory counts the reloads in the always-on
    ``tpfl_compile_cache_warm_total`` counter so cold-start cost is
    measurable in production. "" (default) leaves JAX's cache
    configuration untouched. ``JAX_COMPILATION_CACHE_DIR`` in the
    environment WINS over this knob (profiling.compile_cache_dir is the
    one rule): a cache placed from outside is never re-pointed. Read
    at engine construction."""

    CHECKPOINT_DIR: str = ""
    """Directory for engine-state checkpoints
    (tpfl.management.checkpoint.EngineCheckpointer): when set,
    ``FederationLearner.fit`` snapshots the engine federation state —
    params/variates/aux as UNPADDED host rows (mesh-agnostic: a
    checkpoint written on a 1×1 mesh restores onto 4×2 and back),
    plus the FedBuff schedule position, AsyncController trajectory,
    quarantine/probation state, membership slots and RNG seed — every
    ``CHECKPOINT_EVERY_WINDOWS`` windows, atomically
    pointer-published (the same LATEST discipline as node
    checkpoints). "" (default): no engine checkpointing. Read per
    fit() call."""

    CHECKPOINT_EVERY_WINDOWS: int = 0
    """Snapshot cadence for CHECKPOINT_DIR, in engine windows: every
    K-th window's output state is copied device→host OFF the critical
    path (the snapshot rides the window pipeline's
    ``copy_to_host_async`` host leg, landing while the next window's
    device work runs) and written as a checkpoint. 0 (default)
    disables cadence snapshots even when CHECKPOINT_DIR is set (the
    SIGTERM path below can still emit a final checkpoint). What a
    snapshot stalls a window by is not measured on the chip (ROADMAP
    S7). Read per fit() call."""

    CHECKPOINT_ON_SIGTERM: bool = False
    """Preemption hardening: when on (and CHECKPOINT_DIR is set),
    ``FederationLearner.fit`` installs a SIGTERM handler
    (tpfl.management.checkpoint.install_sigterm_checkpoint) that
    drains the flight recorder and emits a final checkpoint of the
    last completed snapshot before chaining the previous handler — a
    preempted host resumes mid-experiment instead of losing the run.
    Main-thread only (the signal module's rule); the handler is
    removed when fit returns. Off by default: shutdown paths stay
    exactly the PR-16 behavior. Read per fit() call."""

    # --- concurrency diagnostics ---
    TRACE_CONTRACTS: bool = False
    """Opt-in runtime trace-contract checking (tpfl.concurrency): every
    compiled program the federation engine caches is stamped with the
    Settings-knob values its cache key was built from
    (``ENGINE_TELEMETRY`` / ``ENGINE_WIRE_CODEC`` / ``WIRE_TOPK_FRAC``
    / ``ENGINE_DONATE``), and every dispatch re-checks the stamp
    against the live resolved values — a mismatch means a cache key
    lost an axis and a STALE compiled program was about to run;
    ``TraceContractError`` names the offending knob and both values.
    The runtime half of ``tools/tpflcheck``'s capture pass (the static
    half proves key totality at review time; this catches what static
    analysis cannot — indirection through dynamic dispatch). Read at
    program BUILD time like ``LOCK_TRACING``; off by default (zero
    wrappers, zero per-dispatch reads)."""

    STATE_CONTRACTS: bool = False
    """Opt-in checkpoint self-verification
    (``tpfl.management.checkpoint``): every ``EngineCheckpointer.save``
    immediately re-loads its own serialized snapshot onto a shadow
    import and compares per-key digests against the live state dict —
    a key that does not survive the serialize/restore round-trip (or
    changes bytes doing so) raises ``StateContractError`` naming the
    field, BEFORE the snapshot is published as LATEST. The runtime
    half of ``tools/tpflcheck``'s state pass (the static half proves
    export/import totality at review time; this catches value-level
    loss static analysis cannot see). Read per save; off by default
    (zero extra serialization work)."""

    RANK_CONTRACTS: bool = False
    """Opt-in multi-host dispatch receipts (``tpfl.parallel.ranksafe``):
    every engine window dispatch appends the digest of its program
    cache key + lowered-HLO fingerprint to an ordered per-process log,
    and ``crosshost.launch`` compares the receipts across ranks —
    divergence fails with the first (rank, ordinal, key) witness
    instead of hanging the fleet on DCN. The runtime half of
    ``tools/tpflcheck``'s rank pass (the static half proves no
    dispatch is rank-gated at review time; receipts catch
    data-dependent divergence). Read per dispatch; off by default
    (zero recording, zero extra traces)."""

    LOCK_TRACING: bool = False
    """Opt-in runtime lock-order tracing (tpfl.concurrency): every lock
    built through ``make_lock`` becomes a ``TracedLock`` that records
    the acquisition graph (lock A held while acquiring lock B ⇒ edge
    A→B, witnessed by the acquiring thread's name), and ``Node.stop``
    asserts the graph is acyclic — a cycle is a latent deadlock, and
    the error carries the witness chain. Read at lock CREATION time, so
    it must be set before nodes are built. Off by default (one
    thread-local append per acquire — fine for chaos/e2e runs, not for
    1000-node profiles). The static half of the same invariant
    runs in CI via ``python -m tools.tpflcheck`` (docs/concurrency.md)."""

    # --- determinism / TPU ---
    SEED: int | None = None
    """Global seed for reproducible experiments (fork feature)."""

    @classmethod
    def set_test_settings(cls) -> None:
        """Aggressive timings for tests — parity with utils/utils.py:39-57."""
        # Profile totality (enforced by tools/tpflcheck's knob lint):
        # every knob any profile tunes is assigned in ALL profiles, so
        # switching profiles mid-process can never leak a value from
        # the previous one (set_scale_settings leaving
        # AGGREGATION_STALL armed inside a later test run was exactly
        # this bug class).
        cls.GRPC_TIMEOUT = 0.5
        cls.HEARTBEAT_PERIOD = 0.5
        cls.HEARTBEAT_TIMEOUT = 2.0
        cls.ELECTION = "vote"
        cls.GOSSIP_PERIOD = 0.0
        cls.TTL = 10
        cls.GOSSIP_MESSAGES_PER_PERIOD = 100
        cls.AMOUNT_LAST_MESSAGES_SAVED = 100
        cls.GOSSIP_MODELS_PERIOD = 0.1
        cls.GOSSIP_MODELS_PER_ROUND = 4
        cls.GOSSIP_EXIT_ON_X_EQUAL_ROUNDS = 10
        cls.TRAIN_SET_SIZE = 4
        cls.SIM_BATCH_WINDOW = 0.05
        cls.VOTE_TIMEOUT = 30.0
        cls.AGGREGATION_TIMEOUT = 30.0
        # Reference behavior: wait the full timeout, close only on full
        # coverage; fast early-stop polling suits short test rounds.
        cls.AGGREGATION_STALL = None
        cls.ROUND_WAIT_POLL = 0.1
        cls.WAIT_HEARTBEATS_CONVERGENCE = 0.2
        cls.GOSSIP_METRICS = True
        cls.LOG_LEVEL = "DEBUG"
        cls.ASYNC_LOGGER = False
        cls.FILE_LOGGER = False
        cls.LOCK_TRACING = False
        cls.TRACE_CONTRACTS = False
        # Contracts ON in tests: every checkpoint save shadow-verifies
        # its own round-trip and every engine dispatch logs its
        # program digest — the suite exercises both runtime halves
        # continuously, so a totality regression fails loudly here
        # before it ever reaches a fleet.
        cls.STATE_CONTRACTS = True
        cls.RANK_CONTRACTS = True
        # Exactness first in tests: dense payloads (v3 zero-copy layout
        # — still exact), no residual gossip; codec tests opt in
        # explicitly. Zero-copy stays byte-path (INPROC_ZERO_COPY off)
        # and aggregation folds in canonical order at round close
        # (AGG_STREAM_EAGER off) so seeded runs are bit-reproducible;
        # the zero-copy/eager tests toggle both per-case.
        cls.WIRE_CODEC = "dense"
        cls.WIRE_DELTA = False
        cls.WIRE_FORMAT = 3
        cls.WIRE_CHUNK_SIZE = 256 * 1024
        cls.INPROC_ZERO_COPY = False
        cls.AGG_STREAM_EAGER = False
        cls.AGG_MEDIAN_RESERVOIR = 64
        cls.BUFFER_POOL_BUFFERS = 8
        cls.BUFFER_POOL_MAX_BYTES = 256 * 1024 * 1024
        # Fault tolerance: short backoffs (tests run against loopback),
        # fast half-open probes; quorum at reference behavior — chaos
        # tests override per-case.
        cls.RETRY_MAX_ATTEMPTS = 2
        cls.RETRY_BASE_DELAY = 0.05
        cls.RETRY_MAX_DELAY = 0.25
        cls.BREAKER_THRESHOLD = 3
        cls.BREAKER_PROBE_PERIOD = 1.0
        cls.ROUND_QUORUM = 1.0
        # Async rounds off by default (reference-parity sync lifecycle);
        # async tests toggle per-case. Serialized discipline ON
        # for this profile: deferred canonical folds (schedule order
        # when one is attached) keep seeded async runs byte-identical.
        cls.ASYNC_ROUNDS = False
        cls.ASYNC_BUFFER_K = 4
        cls.ASYNC_STALENESS_EXP = 0.5
        cls.ASYNC_ROUND_DEADLINE = 15.0
        cls.ASYNC_SERIALIZED = True
        # Adaptive control off in tests (static PR-10 knobs = reference
        # behavior); controller tests toggle per-case. Untagged
        # contributions stay fresh for parity with pre-async peers.
        cls.ASYNC_ADAPTIVE = False
        cls.ASYNC_K_MIN = 2
        cls.ASYNC_K_MAX = 16
        cls.ASYNC_CTL_EWMA = 0.3
        cls.ASYNC_CTL_QUANTILE = 0.9
        cls.ASYNC_STALENESS_MAX = 16
        cls.ASYNC_UNTAGGED_POLICY = "fresh"
        # Telemetry off in tests by default: tracing tests toggle
        # per-case; the registry records regardless (it is cheap and
        # deterministic).
        cls.TELEMETRY_ENABLED = False
        cls.TELEMETRY_RING = 512
        cls.TELEMETRY_MAX_LABELSETS = 64
        cls.TELEMETRY_DUMP_DIR = ""
        cls.METRIC_MAX_POINTS = 4096
        # Fleet observatory off in tests by default: fleetobs tests
        # arm the publisher/watchdog per-case with explicit dirs,
        # targets and (deterministic) evaluation timestamps.
        cls.FLEETOBS_SNAPSHOT_PERIOD = 0.0
        cls.FLEETOBS_DIR = ""
        cls.SLO_TARGETS = ""
        cls.SLO_EWMA = 0.3
        cls.SLO_BREACH_WINDOWS = 2
        # Device-plane profiling off by default (profiling tests
        # toggle per-case); a low storm
        # threshold would misfire on tests that legitimately churn
        # shapes, so the class default rides.
        cls.PROFILING_ENABLED = False
        cls.PROFILING_RECOMPILE_WARN = 8
        cls.PROFILING_TRACE_DIR = ""
        # Learning-plane ledger off by default (ledger tests toggle
        # per-case) — disabled taps add zero
        # device dispatches, keeping seeded runs bit-identical to
        # pre-ledger behavior.
        cls.LEDGER_ENABLED = False
        cls.LEDGER_RING = 1024
        cls.LEDGER_ANOMALY_Z = 6.0
        cls.LEDGER_ANOMALY_COS = 0.0
        cls.LEDGER_ANOMALY_MIN_N = 4
        cls.LEDGER_CONVERGENCE_WINDOW = 5
        # Active defense off by default (quarantine/robust tests
        # toggle per-case) — verdicts change what
        # aggregates, so seeded reference-parity runs keep it off.
        cls.QUARANTINE_ENABLED = False
        cls.QUARANTINE_PROBATION_ROUNDS = 2
        cls.AGG_ROBUST_BUFFER = 64
        cls.ATTACK_NOISE_STD = 0.1
        # Node-axis sharding off in tests: the suite's 8 virtual CPU
        # devices share one host's cores, and single-dispatch rounds
        # keep seeded runs bit-identical to the reference path. The
        # engine tests opt in per-case with explicit meshes/windows.
        cls.SHARD_NODES = False
        cls.SHARD_DEVICES = 0
        cls.SHARD_MODEL = 1
        cls.SHARD_LAYOUT = "auto"
        # Single-process meshes and no population tier in tests —
        # cross-host / cross-device cases force SHARD_HOSTS /
        # POPULATION_CLIENTS per-case.
        cls.SHARD_HOSTS = 1
        cls.POPULATION_CLIENTS = 0
        cls.POPULATION_SAMPLE = 100
        cls.SHARD_ROUNDS_PER_DISPATCH = 1
        # Engine-plane telemetry off by default (engine_obs tests
        # toggle per-case): the elided carry
        # keeps the engine's round program byte-identical to the
        # reference path.
        cls.ENGINE_TELEMETRY = False
        # Exactness first in tests (the WIRE_CODEC rule above applies
        # on-device too): dense in-program exchange; codec tests opt in
        # per-case. Donation stays on — it is the production path and
        # never changes numerics (the engine_wire tests pin byte
        # identity vs donate=False).
        cls.ENGINE_WIRE_CODEC = "dense"
        cls.ENGINE_DONATE = True
        # Sequential windows by default in tests — the pipelined path
        # is byte-identical (test_engine_async pins it) but interleaves
        # host work, which single-stepping tests don't want.
        cls.ENGINE_PREFETCH = False
        # Elastic/preemption machinery off by default in tests: fixed
        # membership and no disk traffic keep seeded runs hermetic;
        # the elastic tests opt in per-case with explicit views/dirs.
        cls.ELASTIC_CAPACITY_MIN = 2
        cls.COMPILE_CACHE_DIR = ""
        cls.CHECKPOINT_DIR = ""
        cls.CHECKPOINT_EVERY_WINDOWS = 0
        cls.CHECKPOINT_ON_SIGTERM = False

    @classmethod
    def set_standalone_settings(cls) -> None:
        """Single-host many-node simulation profile — parity with
        examples/mnist.py:43-70."""
        cls.GRPC_TIMEOUT = 2.0
        cls.HEARTBEAT_PERIOD = 10.0
        cls.HEARTBEAT_TIMEOUT = 45.0
        cls.ELECTION = "vote"
        cls.GOSSIP_PERIOD = 1.0
        cls.TTL = 40
        cls.GOSSIP_MESSAGES_PER_PERIOD = 9999999
        cls.AMOUNT_LAST_MESSAGES_SAVED = 9999999
        cls.GOSSIP_MODELS_PERIOD = 1.0
        cls.GOSSIP_MODELS_PER_ROUND = 4
        cls.GOSSIP_EXIT_ON_X_EQUAL_ROUNDS = 30
        cls.TRAIN_SET_SIZE = 4
        cls.SIM_BATCH_WINDOW = 0.2
        cls.VOTE_TIMEOUT = 1200.0
        cls.AGGREGATION_TIMEOUT = 1200.0
        cls.AGGREGATION_STALL = None
        cls.ROUND_WAIT_POLL = 0.5
        cls.WAIT_HEARTBEATS_CONVERGENCE = 4.0
        cls.GOSSIP_METRICS = True
        cls.LOG_LEVEL = "INFO"
        cls.ASYNC_LOGGER = True
        cls.FILE_LOGGER = True
        cls.WIRE_CHUNK_SIZE = 256 * 1024
        cls.LOCK_TRACING = False
        cls.TRACE_CONTRACTS = False
        cls.STATE_CONTRACTS = False
        cls.RANK_CONTRACTS = False
        # Single-host, handful of nodes: bytes are not the bottleneck —
        # keep the exact dense wire (reference-parity behavior; the v3
        # layout is exact, only the framing differs). By-reference
        # handoff and eager accumulation stay off: reference parity
        # over speed in this profile, and close-time sorted folds keep
        # seeded runs bit-reproducible.
        cls.WIRE_CODEC = "dense"
        cls.WIRE_DELTA = False
        cls.WIRE_FORMAT = 3
        cls.INPROC_ZERO_COPY = False
        cls.AGG_STREAM_EAGER = False
        cls.AGG_MEDIAN_RESERVOIR = 64
        cls.BUFFER_POOL_BUFFERS = 8
        cls.BUFFER_POOL_MAX_BYTES = 256 * 1024 * 1024
        # Fault tolerance: patient backoffs matching the long protocol
        # timeouts; quorum at reference behavior.
        cls.RETRY_MAX_ATTEMPTS = 3
        cls.RETRY_BASE_DELAY = 0.2
        cls.RETRY_MAX_DELAY = 2.0
        cls.BREAKER_THRESHOLD = 3
        cls.BREAKER_PROBE_PERIOD = 15.0
        cls.ROUND_QUORUM = 1.0
        # Async rounds opt-in here too; the patient deadline matches
        # this profile's long protocol timeouts, and the serialized
        # discipline keeps seeded runs reproducible.
        cls.ASYNC_ROUNDS = False
        cls.ASYNC_BUFFER_K = 4
        cls.ASYNC_STALENESS_EXP = 0.5
        cls.ASYNC_ROUND_DEADLINE = 120.0
        cls.ASYNC_SERIALIZED = True
        # Adaptive control is an opt-in diagnostic here (like tracing):
        # a handful of nodes on one host rarely needs tuned knobs, and
        # static knobs keep seeded runs reference-comparable.
        cls.ASYNC_ADAPTIVE = False
        cls.ASYNC_K_MIN = 2
        cls.ASYNC_K_MAX = 16
        cls.ASYNC_CTL_EWMA = 0.3
        cls.ASYNC_CTL_QUANTILE = 0.9
        cls.ASYNC_STALENESS_MAX = 16
        cls.ASYNC_UNTAGGED_POLICY = "fresh"
        # Tracing is an opt-in diagnostic (enable for a run you intend
        # to traceview); the ring and caps stay at class defaults.
        cls.TELEMETRY_ENABLED = False
        cls.TELEMETRY_RING = 512
        cls.TELEMETRY_MAX_LABELSETS = 64
        cls.TELEMETRY_DUMP_DIR = ""
        cls.METRIC_MAX_POINTS = 4096
        # Fleet observatory: an interactive single host IS its own
        # fleet — no periodic snapshot publisher, no standing SLOs;
        # point FLEETOBS_DIR/SLO_TARGETS at an experiment explicitly.
        cls.FLEETOBS_SNAPSHOT_PERIOD = 0.0
        cls.FLEETOBS_DIR = ""
        cls.SLO_TARGETS = ""
        cls.SLO_EWMA = 0.3
        cls.SLO_BREACH_WINDOWS = 2
        # Profiling is an opt-in diagnostic here, like tracing: enable
        # it (or pass the CLI's --profile) for a run you intend to
        # read attribution/traces from.
        cls.PROFILING_ENABLED = False
        cls.PROFILING_RECOMPILE_WARN = 8
        cls.PROFILING_TRACE_DIR = ""
        # Ledger is an opt-in diagnostic here too — enable it for runs
        # whose per-peer contribution stats / anomaly flags you intend
        # to read (traceview --ledger).
        cls.LEDGER_ENABLED = False
        cls.LEDGER_RING = 1024
        cls.LEDGER_ANOMALY_Z = 6.0
        cls.LEDGER_ANOMALY_COS = 0.0
        cls.LEDGER_ANOMALY_MIN_N = 4
        cls.LEDGER_CONVERGENCE_WINDOW = 5
        # Active defense is opt-in here too: enable QUARANTINE_ENABLED
        # (with the ledger) for runs expected to contain adversaries.
        cls.QUARANTINE_ENABLED = False
        cls.QUARANTINE_PROBATION_ROUNDS = 2
        cls.AGG_ROBUST_BUFFER = 64
        cls.ATTACK_NOISE_STD = 0.1
        # Single-host handful-of-nodes parity profile: one device, one
        # dispatch per round (reference behavior first).
        cls.SHARD_NODES = False
        cls.SHARD_DEVICES = 0
        cls.SHARD_MODEL = 1
        cls.SHARD_LAYOUT = "auto"
        # One process, resident nodes only: no cross-host axis, no
        # cross-device population — the reference P2P layout.
        cls.SHARD_HOSTS = 1
        cls.POPULATION_CLIENTS = 0
        cls.POPULATION_SAMPLE = 100
        cls.SHARD_ROUNDS_PER_DISPATCH = 1
        # Engine telemetry is an opt-in diagnostic here, like tracing/
        # profiling: enable it for engine-window runs you intend to
        # read attribution / convergence / ledger verdicts from.
        cls.ENGINE_TELEMETRY = False
        # Reference parity over bytes on a single host: the exchange
        # stays exact-dense in-program, and donation (numerics-free)
        # stays on.
        cls.ENGINE_WIRE_CODEC = "dense"
        cls.ENGINE_DONATE = True
        # Interactive single-host runs: the free-running driver only
        # helps once windows carry real work; opt in per-experiment.
        cls.ENGINE_PREFETCH = False
        # Elastic/preemption machinery opt-in here like the other ops
        # knobs: point CHECKPOINT_DIR/COMPILE_CACHE_DIR at durable
        # paths for runs you intend to preempt and resume.
        cls.ELASTIC_CAPACITY_MIN = 2
        cls.COMPILE_CACHE_DIR = ""
        cls.CHECKPOINT_DIR = ""
        cls.CHECKPOINT_EVERY_WINDOWS = 0
        cls.CHECKPOINT_ON_SIGTERM = False

    @classmethod
    def set_scale_settings(cls) -> None:
        """Single-host simulation at 100+ nodes: message throttles and
        protocol timeouts sized so control floods and model diffusion
        scale with the node count (the test/standalone profiles assume
        single-digit federations)."""
        # O(N²) vote flooding is the measured scale killer (500-node
        # vote runs take ~6x longer than hash-election runs on one
        # host); deterministic sortition is the profile default. The
        # GLOBAL default stays "vote" for reference parity.
        cls.ELECTION = "hash"
        # Knobs this profile never tuned are pinned at their class
        # defaults (profile totality — see set_test_settings).
        cls.GRPC_TIMEOUT = 10.0
        cls.GOSSIP_PERIOD = 0.0
        cls.TTL = 10
        cls.GOSSIP_MESSAGES_PER_PERIOD = 100_000
        cls.AMOUNT_LAST_MESSAGES_SAVED = 100_000
        # 0.25 s (not 0.05): every push tick's delivery runs the
        # receiver's decode + jitted add_model in the sender's thread;
        # at 0.05 s the 10 trainers' mutual exchange re-pushed
        # payloads ~20x/s each and the redundant deliveries serialized
        # on the GIL + device dispatch for minutes (measured at 1000
        # nodes: 6 min to exchange 10 partials).
        cls.GOSSIP_MODELS_PERIOD = 0.25
        cls.GOSSIP_MODELS_PER_ROUND = 20
        cls.GOSSIP_EXIT_ON_X_EQUAL_ROUNDS = 20
        # Safety net, not the normal exit: with coverage announcements
        # going DIRECTLY to train-set peers the exchange completes
        # coverage in seconds; the stall fires only when an elected
        # peer genuinely never delivers. 60 s keeps slow-but-alive
        # peers in (a 30 s stall measurably fractured the aggregate
        # when it fired mid-exchange under flood-lagged coverage).
        cls.AGGREGATION_STALL = 60.0
        # Heartbeats TTL-flood through relay hubs: at N nodes each beat
        # costs O(N) relays, so the beat rate — not the timeout — sets
        # the hub's floor load. 10s matches the standalone profile.
        cls.HEARTBEAT_PERIOD = 10.0
        cls.HEARTBEAT_TIMEOUT = 45.0
        cls.TRAIN_SET_SIZE = 4
        cls.SIM_BATCH_WINDOW = 0.2
        cls.VOTE_TIMEOUT = 120.0
        cls.AGGREGATION_TIMEOUT = 120.0
        cls.WAIT_HEARTBEATS_CONVERGENCE = 0.5
        cls.LOG_LEVEL = "INFO"
        cls.ASYNC_LOGGER = False
        cls.FILE_LOGGER = False
        cls.GOSSIP_METRICS = False
        cls.WIRE_CHUNK_SIZE = 256 * 1024
        cls.LOCK_TRACING = False
        cls.TRACE_CONTRACTS = False
        # Scale keeps both contract verifiers OFF: the shadow re-import
        # doubles checkpoint serialization work and the dispatch
        # receipts add a trace per cache key — diagnostics a production
        # fleet arms selectively, not a standing tax.
        cls.STATE_CONTRACTS = False
        cls.RANK_CONTRACTS = False
        # Hundreds of round-result waiters waking 2x/s each is a
        # standing GIL tax on the trainers forming the aggregate they
        # wait for; the event still wakes them INSTANTLY on FullModel
        # arrival — this bounds only early-stop detection latency.
        cls.ROUND_WAIT_POLL = 2.0
        # The 1000-node runs are gossip-bound, not compute-bound:
        # quantize + DEFLATE the weight payloads (~4-5x fewer bytes at
        # convergence within noise — tests/test_compression.py's
        # seeded A/B) and ship
        # round results as residuals against the previous round's
        # aggregate wherever the peer acknowledged holding it.
        cls.WIRE_CODEC = "quant8+zlib"
        cls.WIRE_DELTA = True
        cls.WIRE_FORMAT = 3
        # 1000 co-located nodes share one address space: hand model
        # payloads across by reference (no encode/decode/memcpy per
        # hop) and fold contributions into the on-device accumulator
        # as they arrive — together these make the round memcpy-free
        # between fit and finalize. (Dense fallback payloads that DO
        # encode — codec nacks, gRPC peers — stage through the
        # per-node BufferPool instead of allocating per tick.)
        cls.INPROC_ZERO_COPY = True
        cls.AGG_STREAM_EAGER = True
        cls.AGG_MEDIAN_RESERVOIR = 64
        cls.BUFFER_POOL_BUFFERS = 8
        cls.BUFFER_POOL_MAX_BYTES = 256 * 1024 * 1024
        # Fault tolerance: only one retry — backoff sleeps run on
        # contended sender threads (gossiper/heartbeater share the GIL
        # with 1000 in-process nodes), and the breaker caps what a dead
        # hub can cost regardless. Quorum stays 1.0: the stall exit
        # (AGGREGATION_STALL above) already handles absent peers and —
        # unlike an eager quorum — waits for intake to go quiet first.
        cls.RETRY_MAX_ATTEMPTS = 2
        cls.RETRY_BASE_DELAY = 0.1
        cls.RETRY_MAX_DELAY = 1.0
        cls.BREAKER_THRESHOLD = 3
        cls.BREAKER_PROBE_PERIOD = 30.0
        cls.ROUND_QUORUM = 1.0
        # Async rounds are opt-in even at scale (the sync lifecycle is
        # the measured-baseline path), but when enabled this profile
        # runs truly FREE-RUNNING: eager arrival-order folds, a wider
        # buffer for the bigger fleets, and a deadline sized to the
        # stall-window delivery bound (AGGREGATION_STALL's sizing rule
        # applies to it unchanged).
        cls.ASYNC_ROUNDS = False
        cls.ASYNC_BUFFER_K = 8
        cls.ASYNC_STALENESS_EXP = 0.5
        cls.ASYNC_ROUND_DEADLINE = 60.0
        cls.ASYNC_SERIALIZED = False
        # Free-running fleets are what the adaptive controller is FOR:
        # the static K/deadline that fit a 10-node fleet starve
        # or barrier a 1000-node one, so when async is enabled at scale
        # the knobs tune themselves from the observed arrival cadence.
        # Untagged contributions fold at the maximum discount — at this
        # scale an untagged (or tag-stripping) minority must not carry
        # full-weight mass into every buffer.
        cls.ASYNC_ADAPTIVE = True
        cls.ASYNC_K_MIN = 2
        cls.ASYNC_K_MAX = 32
        cls.ASYNC_CTL_EWMA = 0.3
        cls.ASYNC_CTL_QUANTILE = 0.9
        cls.ASYNC_STALENESS_MAX = 16
        cls.ASYNC_UNTAGGED_POLICY = "max-stale"
        # At 1000 in-process nodes every span append shares the GIL
        # with the federation itself: tracing stays off (the <5%
        # measured overhead is per-node, not per-host), the ring
        # shrinks (1000 rings x 512 spans is real memory), and the
        # label cap guards against per-peer label explosions.
        cls.TELEMETRY_ENABLED = False
        cls.TELEMETRY_RING = 128
        cls.TELEMETRY_MAX_LABELSETS = 64
        cls.TELEMETRY_DUMP_DIR = ""
        cls.METRIC_MAX_POINTS = 4096
        # Scale is what the fleet plane is FOR, but the publisher
        # still needs an operator-provided shared dir (a deployment
        # decision, like CHECKPOINT_DIR): a 30 s cadence costs one
        # registry fold + one small JSON write per period once armed.
        # SLOs are per-deployment numbers — no universal default.
        cls.FLEETOBS_SNAPSHOT_PERIOD = 30.0
        cls.FLEETOBS_DIR = ""
        cls.SLO_TARGETS = ""
        cls.SLO_EWMA = 0.3
        cls.SLO_BREACH_WINDOWS = 2
        # 1000 in-process nodes: per-call signature probes and round
        # spans share the GIL with the federation — profiling stays an
        # explicit opt-in, and a higher storm threshold tolerates the
        # wider legitimate shape variety (many partition sizes).
        cls.PROFILING_ENABLED = False
        cls.PROFILING_RECOMPILE_WARN = 16
        cls.PROFILING_TRACE_DIR = ""
        # Ledger off at 1000 in-process nodes for the same GIL/ring-
        # memory reasons as tracing; the ring shrinks when enabled
        # ad hoc (1000 rings x 1024 entries is real memory).
        cls.LEDGER_ENABLED = False
        cls.LEDGER_RING = 256
        cls.LEDGER_ANOMALY_Z = 6.0
        cls.LEDGER_ANOMALY_COS = 0.0
        cls.LEDGER_ANOMALY_MIN_N = 4
        cls.LEDGER_CONVERGENCE_WINDOW = 5
        # At 1000 in-process nodes the live-scoring dispatch per intake
        # shares the one device queue with the vmapped fits — active
        # defense stays an explicit opt-in at this profile's scale.
        cls.QUARANTINE_ENABLED = False
        cls.QUARANTINE_PROBATION_ROUNDS = 2
        cls.AGG_ROBUST_BUFFER = 64
        cls.ATTACK_NOISE_STD = 0.1
        # Scale is where the pod-scale engine earns its keep: spread
        # the node axis over every visible chip (no-op on one device)
        # and fold 8 rounds into each dispatch — where a dispatch
        # round trip outweighs a ~3 ms sim1000-shape round (pre-PR-1
        # figures, not re-measured), per-round dispatch is the
        # dominant wall term the window removes. Trade-off: fit
        # interrupts land between windows, and the arrival-order
        # eager-fold caveat (AGG_STREAM_EAGER above) applies to
        # cross-window reproducibility the same way.
        cls.SHARD_NODES = True
        cls.SHARD_DEVICES = 0
        # Model axis off by default even at scale: the zoo's small
        # models fit one chip, and nodes-axis throughput is the
        # scale profile's first-order win. Raise SHARD_MODEL (a
        # divisor of the device count) to federate models bigger
        # than one chip's HBM; the layout then comes from the module
        # ("auto" = zoo transformer rules, MLP/CNN replicated).
        cls.SHARD_MODEL = 1
        cls.SHARD_LAYOUT = "auto"
        # Auto cross-host: a process launched under
        # jax.distributed (tpfl.parallel.distributed) contributes one
        # hosts-axis slot per participating process; a lone process
        # resolves to hosts=1 and lowers the single-host programs
        # unchanged. Population tier stays opt-in even at scale — set
        # POPULATION_CLIENTS to the registered census to turn the
        # resident nodes into edge aggregators sampling
        # POPULATION_SAMPLE leaf clients per round.
        cls.SHARD_HOSTS = 0
        cls.POPULATION_CLIENTS = 0
        cls.POPULATION_SAMPLE = 100
        cls.SHARD_ROUNDS_PER_DISPATCH = 8
        # At scale the engine IS the federation — without the carry an
        # 8-round window is one opaque dispatch none of the planes can
        # see into — but the fan-out's host work is per-node-per-round,
        # so like the other observability knobs it stays an explicit
        # opt-in at this profile's node counts.
        cls.ENGINE_TELEMETRY = False
        # The scale profile already ships quant8 on the host wire
        # (WIRE_CODEC above) — the in-program exchange follows suit:
        # cross-host/sharded gossip psums int8-round-tripped tensors
        # natively (~4x fewer exchange bytes at loss parity,
        # tests/test_engine_wire.py). Donation on: O(1)-model HBM per window.
        cls.ENGINE_WIRE_CODEC = "quant8"
        cls.ENGINE_DONATE = True
        # 8-round windows carry enough device work to hide the host
        # legs behind — free-running is the point of this profile:
        # dispatch RTT, telemetry fan-out and batch staging all
        # overlap device compute (byte-identical either way).
        cls.ENGINE_PREFETCH = True
        # Long-running fleets resize and get preempted — the scale
        # profile keeps the elastic floor at 2 (first promotion cheap)
        # and SIGTERM hardening ON so a preempted host leaves a final
        # checkpoint; the dirs stay empty (operator-provided paths —
        # durable storage is a deployment decision, not a profile's).
        cls.ELASTIC_CAPACITY_MIN = 2
        cls.COMPILE_CACHE_DIR = ""
        cls.CHECKPOINT_DIR = ""
        cls.CHECKPOINT_EVERY_WINDOWS = 0
        cls.CHECKPOINT_ON_SIGTERM = True

    @classmethod
    def snapshot(cls) -> dict[str, Any]:
        """Capture all settings (for restoring after tests)."""
        return {
            k: getattr(cls, k)
            for k in dir(cls)
            if k.isupper() and not k.startswith("_")
        }

    @classmethod
    def restore(cls, snap: dict[str, Any]) -> None:
        for k, v in snap.items():
            setattr(cls, k, v)

    @classmethod
    def from_env(cls) -> None:
        """Override any setting from a ``TPFL_<NAME>`` environment variable."""
        for k in list(cls.snapshot()):
            env = os.environ.get(f"TPFL_{k}")
            if env is None:
                continue
            cur = getattr(cls, k)
            if isinstance(cur, bool):
                setattr(cls, k, env.lower() in ("1", "true", "yes"))
            elif isinstance(cur, int):
                setattr(cls, k, int(env))
            elif isinstance(cur, float):
                setattr(cls, k, float(env))
            elif cur is None:
                # None-default settings (e.g. SEED): parse numerically when
                # possible so TPFL_SEED=42 yields an int, not a string.
                for parse in (int, float):
                    try:
                        setattr(cls, k, parse(env))
                        break
                    except ValueError:
                        continue
                else:
                    setattr(cls, k, env)
            else:
                setattr(cls, k, env)
