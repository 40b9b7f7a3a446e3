"""Benchmark: FedAvg rounds/sec + samples/sec/chip + MFU on real images.

The driver-defined north-star (BASELINE.json): a 100-node FedAvg CIFAR-10
federation. The reference (p2pfl) runs each node as a Ray-actor process
with pickled-numpy weight exchange and publishes no numbers; its
implicit envelope is the test/example budget (2-node 2-round MNIST in
<= 240 s, examples <= 3600 s — BASELINE.md). Here one full federated
round (100 nodes x 1 local epoch + exact FedAvg) is a single XLA
program on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", "extra"} — and EXITS NON-ZERO
when any requested tier recorded an ``extra.<tier>_error``. The five
tiers that report device rates (primary, resnet, attention, transformer,
sim1000) refuse to run without a TPU (``tpfl.parallel.require_chip``).
- value: local-epoch samples/sec/chip across the federation, measured on
  RENDERED DIGIT IMAGES (real vision data, rendered.py — not noise).
- vs_baseline: measured rounds/sec over the reference envelope's floor
  (2 rounds / 240 s, the only quantitative anchor the reference gives).
- extra.mfu: model FLOPs utilization, computed from the ANALYTIC model
  flops of the CNN (2·M·K·N per conv/dense layer, x3 for fwd+bwd —
  printed as extra.round_tflops) over DEVICE time. Timing note: the
  bench runs K rounds inside ONE jitted ``fori_loop`` dispatch and
  subtracts a measured empty-call baseline (extra.dispatch_rtt_ms), so
  a host whose dispatch round trip is the size of a round cannot be
  read as a slow device. What that round trip IS on the current chip
  host is printed by ``chip_smoke.py``'s ``sync`` phase.
- extra.mfu_floor / extra.mfu_vs_floor: the fundamental ceiling for
  this model/batch — an identical SHARED-weight training step (no
  per-node weights at all) — is MEASURED in-bench each run, and the
  federated round's MFU is reported as a ratio of it. Context (see
  docs/perf_cnn.md): the r3 verdict's 25% target is not reachable for
  this model shape on v5e by ANY formulation tried (im2col batched
  GEMMs 4.1%, custom GEMM backward 2.7%, Pallas im2col backward
  kernels 2.4%, forward-style-conv backward 11.3% — the shipped
  default); the floor measured 12.0% in r4. The framework's MFU
  headroom on MXU-friendly models is evidenced by the ResNet-18 tier.
- extra.resnet18_*: BASELINE config 3 tier (ResNet-18 w/ BatchNorm via
  the aux-threaded vmapped path, CIFAR-100-shaped) — benched with all
  three named aggregation algorithms: FedAvg (resnet18_cfg3_*),
  SCAFFOLD (resnet18_scaffold_*), FedProx (resnet18_fedprox_*), each
  with samples/s/chip and model-flops MFU.
- extra.*_fwdbwd_*_toks_per_sec: long-context training throughput —
  standalone flash kernel vs XLA blockwise, plus the sequence-parallel
  ring path (ring_sp_flash vs ring_sp_xla on a 1-device sp mesh: same
  ring machinery, different inner).
- extra.sim1000_*: BASELINE config 4 tier (1000 nodes, 10% partial
  participation per round, masked vmapped federation).
- extra.multichip.*: pod-scale federation engine tier
  (tpfl/parallel/engine.py) — sim1000 promoted to a `nodes` mesh: one
  sharded XLA program per R_WIN-round window (gossip exchange + fold
  lowered to psum collectives over ICI, host dispatch RTT paid once
  per window). Reports rounds/sec at 1 and all devices
  (rps_by_devices), scaling_efficiency = (rps_N/rps_1)/N, the
  engine-vs-legacy single-device ratio, same-seed byte-determinism at
  fixed device count, window-vs-sequential equivalence, the live
  tpfl_mfu{program="engine"} gauge, and the sim100k cross-device
  smoke: 100k registered clients, K sampled per round, peak host
  memory O(active) (rss_bounded). See docs/scaling.md.
- extra.wire_*: wire codec tier — dense-vs-codec payload bytes and
  encode/decode throughput on the flagship CNN params, plus
  extra.wire_ab: a seeded 4-node digits FedAvg run twice (dense v1
  wire vs the scale profile's "quant8+zlib" + residual broadcast),
  reporting total payload bytes, steady loss for both runs, and the
  ≥4x-bytes / ≤2%-loss acceptance booleans.
- extra.telemetry_*: telemetry tier (management/telemetry + tracing) —
  trace-id mint determinism for a fixed seed, a seeded 4-node digits
  A/B with hop-level tracing off vs on (must cost <5% rounds/sec, and
  the traced run's spans must reconstruct complete payload hop paths
  across all nodes via tools/traceview.py), and a registry fold sanity
  report.
- extra.chaos_*: chaos tier (communication/faults.py) —
  chaos_determinism drives a fixed message schedule through the seeded
  FaultInjector twice and reports per-round delivered/dropped counts
  (identical for identical (seed, plan)); chaos_ab runs the seeded
  digits federation fault-free and under 20% per-attempt drop with one
  trainer crashed mid-round, reporting per-round wall time (must stay
  under AGGREGATION_TIMEOUT — quorum degradation) and final loss (must
  land within 5% of fault-free).

- extra.async_*: asynchronous buffered rounds tier
  (stages.AsyncRoundStage / Settings.ASYNC_ROUNDS) — async_ab runs the
  seeded 10-node digits federation under a TrainerSpeedPlan with a
  10x-slower 20% tail, sync-vs-async: async must beat the barrier'd
  sync lifecycle by >=1.5x rounds/sec at steady loss within 2%;
  async_determinism runs the SERIALIZED discipline (plan-seeded
  AsyncSchedule reorder buffers) twice with one seed — with the
  ADAPTIVE controller on (learning/async_control.py) — and asserts
  byte-identical final global models across runs and across nodes,
  plus identical per-node controller K/deadline trajectories. The
  stale-flooding defense variant lives in extra.byzantine_async.

- extra.engine_wire_*: device-side wire codec + donation tier
  (Settings.ENGINE_WIRE_CODEC / ENGINE_DONATE, tpfl/parallel/engine.py
  + tpfl/learning/compression.py) — engine_wire_program: codec-off
  HLO-digest stability across a codec toggle (dense lowers the
  byte-identical pre-codec program), donating-program outputs
  byte-identical to donate=False, and the compiled-HLO donation
  inspection clean (every donated state leaf aliases an output
  buffer); engine_wire_bytes: dense-vs-quant8 per-round exchange
  bytes from the device-side telemetry carry (gate >= 3x fewer);
  engine_wire_parity: seeded windowed A/B, quantized steady loss
  within 2% of dense.

- extra.profiling_*: device-plane observatory tier
  (management/profiling.py) — CompileObservatory recompile detection on
  a shape-churn probe, a seeded 4-node digits A/B with
  PROFILING_ENABLED off vs on (<5% rounds/sec budget, and the profiled
  run's per-round attribution — train/dispatch/fold/gossip/host_other
  — must cover ≥95% of each round's wall), and the live-MFU gauge vs
  the analytic MFU column (one CostModel path, must agree within 5%).

``--profile <dir>`` wraps the primary timed region in a
``jax.profiler`` trace (the TPU-native analog of the reference's opt-in
yappi hooks, ``examples/mnist.py:264-297``); view with TensorBoard or
xprof. Any federation run can now do the same via
``tpfl experiment run --profile <dir>`` / ``Settings.PROFILING_TRACE_DIR``.

``--tiers a,b,...`` selects tiers (default ``all``); the non-device
tiers (serde/chaos/analysis/telemetry/profiling/ledger/byzantine) are
CPU-safe, which is what the CI perf-smoke job runs.

``--check BASELINE.json`` is the perf REGRESSION GATE
(tpfl.management.profiling.compare_to_baseline): after the selected
tiers run, the parsed metrics are compared against the committed
baseline's per-metric tolerance thresholds; the machine-readable
verdict rides ``extra.check`` and the exit code is nonzero on any
regression. With ``--results RUN.json`` the gate compares an existing
bench output instead of running anything (fast path; no jax import).
"""

from __future__ import annotations

import argparse
import json
import time


class _MultichipDone(Exception):
    """Control-flow sentinel: the multichip tier delegated to a forced
    8-virtual-device subprocess and grafted its result."""


def _peak_flops(device) -> float | None:
    """Thin wrapper over :data:`tpfl.management.profiling.PEAK_FLOPS`
    (the one copy of the per-device-kind peak table)."""
    from tpfl.management.profiling import peak_flops

    return peak_flops(device)


def _flops_of(compiled) -> float | None:
    """Thin wrapper over ``CostModel.xla_flops`` — ONE
    ``cost_analysis()`` call path (and one scan-counted-once caveat,
    documented there) shared with ``parallel/scaling.py``, so static
    scaling analysis and live MFU can never disagree."""
    from tpfl.management.profiling import cost_model

    return cost_model.xla_flops(compiled)


def _round_flops_estimate(fed_factory, input_shape, batch_shape, n_nodes,
                          n_batches, epochs, aux=False) -> float | None:
    """Model flops of one federated round, counting-semantics-proof:
    compile a 1-node 1-batch-step program on the default device and
    scale analytically (x nodes x batch-steps x epochs). The per-round
    aggregation (a weighted tree-sum, O(params)) is negligible next to
    the train steps and is not scaled in."""
    import jax.numpy as jnp

    fed1 = fed_factory(1)
    xs1 = jnp.zeros((1, 1, *batch_shape), jnp.bfloat16)
    ys1 = jnp.zeros((1, 1, batch_shape[0]), jnp.int32)
    w1 = jnp.ones((1,), jnp.float32)
    try:
        if aux:
            p1, a1 = fed1.init_state(input_shape)
            fn = fed1._build_round_aux()
            compiled = fn.lower(p1, a1, xs1, ys1, w1, 1).compile()
        else:
            p1 = fed1.init_params(input_shape)
            fn = fed1._build_round()
            compiled = fn.lower(p1, xs1, ys1, w1, 1).compile()
    except Exception:
        return None
    f1 = _flops_of(compiled)
    if not f1:
        return None
    return f1 * n_nodes * n_batches * epochs


def _serde_tier(extra: dict, cnn_host_params) -> None:
    """Zero-copy model plane tier. Three reports:

    - extra.serde: v1 (legacy dense msgpack) vs v3 (pooled header +
      contiguous payload, zero-copy decode views) encode/decode
      throughput in GB/s of dense payload, on the digits MLP (the
      protocol e2e model) and the flagship CNN params, plus the ≥2x
      round-trip acceptance boolean.
    - extra.serde_agg_peak: aggregation peak-RSS DELTA (beyond holding
      the contributions themselves) for a 2- vs 64-contributor FedAvg
      round, measured in a fresh subprocess each (ru_maxrss is a
      high-water mark) — the streaming donated accumulator keeps it
      O(1 model), flat in N.
    - extra.serde_inproc_ab: a seeded 4-node in-memory digits
      federation run with the byte path and again with
      Settings.INPROC_ZERO_COPY (model payloads handed across by
      reference): rounds/sec both ways and the final-loss rel diff
      (must be ~0 — the ref path is exact).

    The sim1000 tier above is unchanged by the zero-copy plane (it
    times the vmapped round program, no serialization in the loop);
    its number riding in the same BENCH line is the no-regression
    check.
    """
    import json as _json
    import os as _os
    import subprocess
    import sys as _sys

    import numpy as np

    from tpfl.learning import serialization as ser

    try:
        rng = np.random.default_rng(0)
        # The digits example's model: the zoo MLP defaults ((256, 128)
        # hidden) on 28x28 input — ~920 KB of payload, what an actual
        # digits-federation gossip push moves.
        digits_params = {
            "dense1": {
                "kernel": rng.normal(size=(784, 256)).astype(np.float32),
                "bias": np.zeros(256, np.float32),
            },
            "dense2": {
                "kernel": rng.normal(size=(256, 128)).astype(np.float32),
                "bias": np.zeros(128, np.float32),
            },
            "dense3": {
                "kernel": rng.normal(size=(128, 10)).astype(np.float32),
                "bias": np.zeros(10, np.float32),
            },
        }

        def _tp(fn, n=5):
            fn()  # warm
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        report = {}
        for name, tree in (("digits_mlp", digits_params), ("cnn", cnn_host_params)):
            v1 = ser.encode_model_payload(tree, ["b"], 1, {})
            v3 = ser.encode_model_payload_v3(tree, ["b"], 1, {})
            gb = len(v1) / 1e9
            te1 = _tp(lambda: ser.encode_model_payload(tree, ["b"], 1, {}))
            te3 = _tp(lambda: ser.encode_model_payload_v3(tree, ["b"], 1, {}))
            td1 = _tp(lambda: ser.decode_model_payload(v1))
            td3 = _tp(lambda: ser.decode_model_payload(v3))
            report[name] = {
                "payload_bytes_v1": len(v1),
                "payload_bytes_v3": len(v3),
                "encode_v1_GBps": round(gb / te1, 3),
                "encode_v3_GBps": round(gb / te3, 3),
                "decode_v1_GBps": round(gb / td1, 3),
                "decode_v3_GBps": round(gb / td3, 3),
                "roundtrip_speedup_v3": round((te1 + td1) / (te3 + td3), 2),
                "ge_2x_roundtrip": bool((te1 + td1) / (te3 + td3) >= 2.0),
            }
        extra["serde"] = report

        # Aggregation peak memory vs contributor count: fresh
        # subprocess per N (ru_maxrss is monotonic within a process).
        child = r"""
import resource, json, sys
import jax, jax.numpy as jnp, numpy as np
from tpfl.learning.model import TpflModel
from tpfl.learning.aggregators import FedAvg
N = int(sys.argv[1]); P = 4_000_000  # 16 MB f32 model
rng = np.random.default_rng(0)
def mk(i):
    return TpflModel(params={"w": jnp.asarray(rng.normal(size=(P,)), jnp.float32)},
                     num_samples=1, contributors=[f"n{i}"])
models = [mk(i) for i in range(N)]
jax.block_until_ready([m.get_parameters()["w"] for m in models])
# Warm the jitted fold (compile + steady accumulator churn) BEFORE the
# baseline snapshot: ru_maxrss is a high-water mark, so the measured
# delta is the MARGINAL memory the N-contributor aggregation adds — an
# O(N x model) stack still shows (it materializes per call); the
# streaming donated fold does not.
warm = FedAvg("warm").aggregate([mk(900), mk(901)])
jax.block_until_ready(jax.tree_util.tree_leaves(warm.get_parameters()))
del warm
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
out = FedAvg("bench").aggregate(models)
jax.block_until_ready(jax.tree_util.tree_leaves(out.get_parameters()))
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({"agg_peak_delta_kb": int(peak - base)}))
"""
        peaks = {}
        for n_contrib in (2, 64):
            proc = subprocess.run(
                [_sys.executable, "-c", child, str(n_contrib)],
                capture_output=True,
                text=True,
                timeout=300,
                # One process per chip: this parent may hold it, so the
                # child (a host-memory measurement) is pinned to the
                # CPU through its environment, before it imports jax.
                env=dict(_os.environ, JAX_PLATFORMS="cpu"),
                cwd=_os.path.dirname(_os.path.abspath(__file__)),
            )
            peaks[n_contrib] = _json.loads(proc.stdout.strip().splitlines()[-1])[
                "agg_peak_delta_kb"
            ]
        # O(1) check: marginal growth for 64 contributors within 1.5x
        # of 2 contributors (+32 MB allocator-noise grace — two model
        # buffers, far below the ~1 GB a 64-wide stack materializes).
        flat = peaks[64] <= 1.5 * peaks[2] + 32768
        extra["serde_agg_peak"] = {
            "model_bytes": 16_000_000,
            "peak_delta_kb_n2": peaks[2],
            "peak_delta_kb_n64": peaks[64],
            "o1_flat_within_1.5x": bool(flat),
        }
    except Exception as e:
        extra["serde_error"] = str(e)[:200]

    # In-process zero-copy A/B: byte path vs by-reference handoff.
    try:
        from tpfl.settings import Settings

        snap = Settings.snapshot()
        try:
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            Settings.ELECTION = "hash"
            Settings.SEED = 4321

            def run(zero_copy: bool) -> dict:
                from tpfl.learning.dataset import (
                    RandomIIDPartitionStrategy,
                    synthetic_mnist,
                )
                from tpfl.models import create_model
                from tpfl.node import Node
                from tpfl.utils import wait_convergence, wait_to_finish

                Settings.INPROC_ZERO_COPY = zero_copy
                Settings.AGG_STREAM_EAGER = zero_copy
                n, rounds = 4, 6
                ds = synthetic_mnist(n_train=200 * n, n_test=60, seed=0, noise=0.8)
                parts = ds.generate_partitions(
                    n, RandomIIDPartitionStrategy, seed=1
                )
                nodes = [
                    Node(
                        create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
                        parts[i],
                        # SAME addresses in both runs: learner shuffle
                        # seeds derive from (Settings.SEED, addr), and
                        # differing addrs would give the two runs
                        # different data orders and an incomparable
                        # loss (the chaos tier pins its addrs for the
                        # same reason). Runs are sequential, so no
                        # registry collision.
                        addr=f"serde-{i}",
                        learning_rate=0.05,
                        batch_size=32,
                    )
                    for i in range(n)
                ]
                for nd in nodes:
                    nd.start()
                try:
                    for nd in nodes[1:]:
                        nodes[0].connect(nd.addr)
                    wait_convergence(nodes, n - 1, only_direct=False, wait=10)
                    t0 = time.monotonic()
                    nodes[0].set_start_learning(rounds=rounds, epochs=1)
                    wait_to_finish(nodes, timeout=240)
                    elapsed = time.monotonic() - t0
                    loss = float(
                        nodes[0].learner.evaluate().get("test_loss", float("nan"))
                    )
                    return {
                        "rounds_per_sec": round(rounds / elapsed, 3),
                        "final_loss": round(loss, 4),
                    }
                finally:
                    for nd in nodes:
                        nd.stop()

            by = run(False)
            zc = run(True)
            rel = abs(zc["final_loss"] - by["final_loss"]) / max(
                abs(by["final_loss"]), 1e-9
            )
            extra["serde_inproc_ab"] = {
                "seed": 4321,
                "byte_path": by,
                "zero_copy": zc,
                "loss_rel_diff": round(rel, 4),
                "loss_within_1pct": bool(rel <= 0.01),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["serde_inproc_error"] = str(e)[:200]


def _chaos_tier(extra: dict) -> None:
    """Chaos tier (communication/faults.py). Two reports:

    - extra.chaos_determinism: a fixed round-structured message
      schedule driven twice through the seeded FaultInjector —
      per-round delivered/dropped counts must come out identical
      (and, being schedule-seeded, identical across bench invocations
      with the same seed/plan).
    - extra.chaos_ab: a live seeded digits federation run fault-free
      and again under 20 % per-attempt drop on every link with one
      trainer crashed mid-round — per-round wall time (must not burn
      AGGREGATION_TIMEOUT: heartbeat loss shrinks the expected
      contributor set) and final loss (must land within 5 % of
      fault-free).
    """
    import numpy as np  # noqa: F401  (kept: symmetry with other tiers)

    from tpfl.communication.faults import FaultInjector, FaultPlan
    from tpfl.settings import Settings

    CHAOS_SEED = 1234
    PLAN = {"links": {"*->*": {"drop": 0.2}}}

    try:
        # (a) Determinism of the fault accounting itself.
        def drive() -> list[list[int]]:
            fi = FaultInjector(FaultPlan.from_dict(PLAN), seed=CHAOS_SEED)
            links = [
                (f"n{i}", f"n{j}") for i in range(3) for j in range(3) if i != j
            ]
            per_round = []
            for _ in range(5):  # rounds
                delivered = dropped = 0
                for _ in range(40):  # messages per link per round
                    for link in links:
                        d = fi.decide(*link)
                        if d.action == "drop":
                            dropped += 1
                        else:
                            delivered += d.copies
                per_round.append([delivered, dropped])
            return per_round

        first, second = drive(), drive()
        extra["chaos_determinism"] = {
            "seed": CHAOS_SEED,
            "per_round_delivered_dropped": first,
            "identical": first == second,
        }

        # (b) Live A/B: fault-free vs 20 % drop + one crashed trainer.
        snap = Settings.snapshot()
        try:
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            Settings.ELECTION = "hash"  # n <= TRAIN_SET_SIZE: all elected
            Settings.SEED = CHAOS_SEED

            def run(inject: bool) -> dict:
                from tpfl.learning.dataset import (
                    RandomIIDPartitionStrategy,
                    synthetic_mnist,
                )
                from tpfl.models import create_model
                from tpfl.node import Node
                from tpfl.utils import wait_convergence, wait_to_finish

                n, rounds = 4, 6
                ds = synthetic_mnist(
                    n_train=200 * n, n_test=60, seed=0, noise=0.8
                )
                parts = ds.generate_partitions(
                    n, RandomIIDPartitionStrategy, seed=1
                )
                nodes = [
                    Node(
                        create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
                        parts[i],
                        # Pinned addresses: learner shuffle seeds derive
                        # from (Settings.SEED, addr) — auto-assigned
                        # addrs increment per protocol instance, which
                        # would give the two runs different data orders
                        # and an incomparable loss.
                        addr=f"chaos-{i}",
                        learning_rate=0.05,
                        batch_size=32,
                    )
                    for i in range(n)
                ]
                fi = None
                if inject:
                    fi = FaultInjector(
                        FaultPlan.from_dict(PLAN), seed=CHAOS_SEED
                    )
                    for nd in nodes:
                        fi.attach(nd.communication)
                for nd in nodes:
                    nd.start()
                try:
                    for nd in nodes[1:]:
                        nodes[0].connect(nd.addr)
                    wait_convergence(nodes, n - 1, only_direct=False, wait=10)
                    t0 = time.monotonic()
                    nodes[0].set_start_learning(rounds=rounds, epochs=1)
                    if inject:
                        # Crash the victim the moment it enters the
                        # FINAL round's train set (before it can
                        # contribute) — survivors must shrink the
                        # expected contributor set and close on the
                        # live members, not wait out the timeout.
                        deadline = time.monotonic() + 60
                        while time.monotonic() < deadline and not (
                            (nodes[-1].state.round or 0) == rounds - 1
                            and nodes[-1].state.train_set
                        ):
                            time.sleep(0.02)
                        fi.crash(nodes[-1].addr)
                    survivors = nodes[:-1] if inject else nodes
                    wait_to_finish(survivors, timeout=240)
                    elapsed = time.monotonic() - t0
                    loss = float(
                        survivors[0].learner.evaluate().get("test_loss", float("nan"))
                    )
                    stats = fi.stats() if fi is not None else {}
                    return {
                        "rounds": rounds,
                        "elapsed_s": round(elapsed, 2),
                        "per_round_s": round(elapsed / rounds, 2),
                        "final_loss": round(loss, 4),
                        "dropped": sum(
                            s.get("dropped", 0) for s in stats.values()
                        ),
                        "delivered": sum(
                            s.get("delivered", 0) for s in stats.values()
                        ),
                    }
                finally:
                    for nd in nodes:
                        nd.stop()

            ff = run(False)
            ch = run(True)
            rel = abs(ch["final_loss"] - ff["final_loss"]) / max(
                abs(ff["final_loss"]), 1e-9
            )
            extra["chaos_ab"] = {
                "plan": "20% drop all links + 1 trainer crashed mid-round",
                "seed": CHAOS_SEED,
                "fault_free": ff,
                "chaos": ch,
                "loss_rel_diff": round(rel, 4),
                "loss_within_5pct": bool(rel <= 0.05),
                "no_timeout_burn": bool(
                    ch["per_round_s"] < Settings.AGGREGATION_TIMEOUT
                ),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["chaos_error"] = str(e)[:200]


def _analysis_tier(extra: dict) -> None:
    """Analysis tier (tools/tpflcheck + tpfl.concurrency). Two reports:

    - extra.analysis_static: wall-time of the full tpflcheck suite
      (guards/locks/capture/spmd/sync/layers/knobs/threads/trace/
      events/donate/wire/state/rank) over the tree — budget < 5 s,
      zero unwaived violations, plus per-pass counts for the
      JAX-semantics passes (capture/spmd/sync) and the ISSUE-19
      state/rank passes (each must be clean — CI-gated).
    - extra.analysis_lock_trace: the same seeded 3-node digits
      federation run with Settings.LOCK_TRACING off and then on —
      the traced run must finish with an ACYCLIC runtime acquisition
      graph, every participating thread NAMED, and <10% round-
      throughput overhead vs untraced.
    """
    import pathlib
    import sys as _sys

    root = pathlib.Path(__file__).resolve().parent
    if str(root) not in _sys.path:
        _sys.path.insert(0, str(root))
    from tpfl.settings import Settings

    try:
        from tools.tpflcheck import (
            check_capture,
            check_rank,
            check_spmd,
            check_state,
            check_sync,
            run_all,
        )

        t0 = time.monotonic()
        violations, waived, warnings, _ = run_all(root)
        wall = time.monotonic() - t0
        # Per-pass violation counts for the JAX-semantics passes
        # (ISSUE 14) — gated alongside the suite-wide zero: a pass
        # whose count creeps up is a regression even while waived.
        t1 = time.monotonic()
        per_pass = {
            "capture": len(check_capture(root)),
            "spmd": len(check_spmd(root)),
            "sync": len(check_sync(root)),
            "state": len(check_state(root)),
            "rank": len(check_rank(root)),
        }
        jax_passes_wall = time.monotonic() - t1
        extra["analysis_static"] = {
            "wall_s": round(wall, 2),
            "within_5s_budget": bool(wall < 5.0),
            "violations": len(violations),
            "zero_violations": not violations,
            "jax_pass_violations": per_pass,
            "jax_passes_clean": not any(per_pass.values()),
            # Per-pass acceptance booleans for the ISSUE-19 passes —
            # the baseline gate can't anchor a count on a 0 baseline,
            # so cleanliness gates as a flag like the suite-wide zero.
            "state_pass_clean": per_pass["state"] == 0,
            "rank_pass_clean": per_pass["rank"] == 0,
            "jax_passes_wall_s": round(jax_passes_wall, 2),
            "waived": len(waived),
            "warnings": len(warnings),
        }

        snap = Settings.snapshot()
        try:
            from tpfl.concurrency import lock_graph
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            Settings.ELECTION = "hash"  # n <= TRAIN_SET_SIZE: all elected
            Settings.SEED = 777

            def run(traced: bool, tag: str) -> dict:
                from tpfl.learning.dataset import (
                    RandomIIDPartitionStrategy,
                    synthetic_mnist,
                )
                from tpfl.models import create_model
                from tpfl.node import Node
                from tpfl.utils import wait_convergence, wait_to_finish

                # Read at lock CREATION time: set before Node() builds
                # its state/protocol/aggregator locks.
                Settings.LOCK_TRACING = traced
                lock_graph.clear()
                n, rounds = 3, 4
                ds = synthetic_mnist(n_train=150 * n, n_test=30, seed=0, noise=0.6)
                parts = ds.generate_partitions(
                    n, RandomIIDPartitionStrategy, seed=1
                )
                nodes = [
                    Node(
                        create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
                        parts[i],
                        addr=f"{tag}-{i}",  # pinned: seeded data order
                        learning_rate=0.05,
                        batch_size=32,
                    )
                    for i in range(n)
                ]
                for nd in nodes:
                    nd.start()
                try:
                    for nd in nodes[1:]:
                        nodes[0].connect(nd.addr)
                    wait_convergence(nodes, n - 1, only_direct=False, wait=10)
                    t0 = time.monotonic()
                    nodes[0].set_start_learning(rounds=rounds, epochs=1)
                    wait_to_finish(nodes, timeout=240)
                    elapsed = time.monotonic() - t0
                finally:
                    for nd in nodes:
                        nd.stop()  # traced runs assert acyclicity here
                out = {
                    "rounds": rounds,
                    "elapsed_s": round(elapsed, 2),
                    "rounds_per_s": round(rounds / elapsed, 3),
                }
                if traced:
                    lock_graph.assert_acyclic()
                    names = sorted(lock_graph.thread_names())
                    out["acyclic"] = True
                    out["runtime_edges"] = len(lock_graph.edges())
                    out["traced_threads"] = len(names)
                    out["all_threads_named"] = not any(
                        t.startswith("Thread-") for t in names
                    )
                    out["thread_roster"] = names[:16]
                return out

            run(False, "lt-warm")  # discarded: pays the jit warmup
            off = run(False, "lt-off")
            on = run(True, "lt-on")
            overhead = 1.0 - on["rounds_per_s"] / max(off["rounds_per_s"], 1e-9)
            extra["analysis_lock_trace"] = {
                "untraced": off,
                "traced": on,
                "overhead_frac": round(overhead, 4),
                "within_10pct_budget": bool(overhead < 0.10),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["analysis_error"] = str(e)[:200]


def _telemetry_tier(extra: dict) -> None:
    """Telemetry tier (management/telemetry + tracing). Three reports:

    - extra.telemetry_determinism: trace-id minting is a pure function
      of (seed, node, ordinal) — two mint sequences for the same seed
      must be identical, and a different seed must diverge.
    - extra.telemetry_ab: the same seeded 4-node digits federation run
      with TELEMETRY_ENABLED off and on — the traced run must cost
      <5% rounds/sec, and its exported spans must reconstruct complete
      payload hop paths (encode on one node -> decode/fold on another)
      via tools.traceview.
    - extra.telemetry_registry: registry fold sanity on the traced run
      (transport counters present, fold wall-time).
    """
    from tpfl.management import tracing
    from tpfl.settings import Settings

    try:
        # (a) Deterministic minting under a fixed seed.
        snap_seed = Settings.SEED
        try:
            Settings.SEED = 4242
            tracing.reset()
            first = [tracing.mint("bench-node") for _ in range(8)]
            tracing.reset()
            second = [tracing.mint("bench-node") for _ in range(8)]
            Settings.SEED = 4243
            tracing.reset()
            other = [tracing.mint("bench-node") for _ in range(8)]
        finally:
            Settings.SEED = snap_seed
            tracing.reset()
        extra["telemetry_determinism"] = {
            "seed": 4242,
            "identical": first == second,
            "seed_sensitive": first != other,
            "sample": first[0],
        }

        # (b) Overhead A/B + timeline completeness.
        snap = Settings.snapshot()
        try:
            from tpfl.management.logger import logger as _logger
            from tpfl.management.telemetry import flight
            from tools.traceview import build_timeline, summarize

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            Settings.ELECTION = "hash"  # n <= TRAIN_SET_SIZE: all elected
            Settings.SEED = 4242

            def run(traced: bool, tag: str) -> dict:
                from tpfl.learning.dataset import (
                    RandomIIDPartitionStrategy,
                    synthetic_mnist,
                )
                from tpfl.models import create_model
                from tpfl.node import Node
                from tpfl.utils import wait_convergence, wait_to_finish

                Settings.TELEMETRY_ENABLED = traced
                flight.clear()
                tracing.reset()
                n, rounds = 4, 5
                ds = synthetic_mnist(
                    n_train=150 * n, n_test=30, seed=0, noise=0.6
                )
                parts = ds.generate_partitions(
                    n, RandomIIDPartitionStrategy, seed=1
                )
                nodes = [
                    Node(
                        create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
                        parts[i],
                        addr=f"{tag}-{i}",  # pinned: seeded data order
                        learning_rate=0.05,
                        batch_size=32,
                    )
                    for i in range(n)
                ]
                for nd in nodes:
                    nd.start()
                try:
                    for nd in nodes[1:]:
                        nodes[0].connect(nd.addr)
                    wait_convergence(nodes, n - 1, only_direct=False, wait=10)
                    t0 = time.monotonic()
                    nodes[0].set_start_learning(rounds=rounds, epochs=1)
                    wait_to_finish(nodes, timeout=240)
                    elapsed = time.monotonic() - t0
                finally:
                    for nd in nodes:
                        nd.stop()
                out = {
                    "rounds": rounds,
                    "elapsed_s": round(elapsed, 2),
                    "rounds_per_s": round(rounds / elapsed, 3),
                }
                if traced:
                    out["timeline"] = summarize(
                        build_timeline(tracing.export())
                    )
                return out

            run(False, "tele-warm")  # discarded: pays the jit warmup
            off = run(False, "tele-off")
            on = run(True, "tele-on")
            overhead = 1.0 - on["rounds_per_s"] / max(off["rounds_per_s"], 1e-9)
            tl = on.pop("timeline")
            extra["telemetry_ab"] = {
                "untraced": off,
                "traced": on,
                "overhead_frac": round(overhead, 4),
                "within_5pct_budget": bool(overhead < 0.05),
                "timeline": tl,
                "hop_paths_reconstructed": bool(
                    tl["complete_traces"] > 0
                    and len(tl["nodes"]) == 4
                ),
            }

            t0 = time.monotonic()
            folded = _logger.metrics.fold()
            extra["telemetry_registry"] = {
                "fold_wall_ms": round((time.monotonic() - t0) * 1e3, 2),
                "counter_series": len(folded["counters"]),
                "gauge_series": len(folded["gauges"]),
                "histogram_series": len(folded["histograms"]),
                "has_transport_counters": any(
                    k[0] == "tpfl_transport_sends_total"
                    for k in folded["counters"]
                ),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["telemetry_error"] = str(e)[:200]



def _crosshost_tier(extra: dict) -> None:
    """3D cross-host engine + million-client population tier (ISSUE 18).

    Three receipts, all CPU-safe:

    - extra.crosshost parity: two REAL ``jax.distributed`` subprocess
      workers (gloo CPU collectives, 4 forced virtual devices each)
      run the seeded demo federation on the auto-resolved 2x4
      ``hosts x nodes`` mesh; both ranks must agree byte-for-byte and
      land allclose to the 1-process 8-device reference — cross-host
      == single-process, machine-checked without TPU.
    - extra.crosshost dcn: the DCN leg's bytes/round under quant8 vs
      dense (the engine's wire codec applied to the cross-host
      partials) must drop >= 3x at <= 2% mean-loss deviation.
    - extra.crosshost.sim1m: 1M registered clients, K=100 sampled per
      round through :class:`tpfl.parallel.ClientPopulation` — rounds/s,
      exchange bytes/round, per-round checkpoint round-trips through
      ``EngineCheckpointer`` restoring EXACTLY the sampled clients'
      records, and peak-RSS growth bounded (state O(active), never
      O(census)).

    The subprocess workers provision their own virtual devices; this
    process' backend is untouched (same reasoning as the multichip
    tier's re-exec).
    """
    try:
        import resource
        import tempfile

        import jax
        import numpy as np

        from tpfl.learning import compression
        from tpfl.management.checkpoint import EngineCheckpointer
        from tpfl.models import MLP
        from tpfl.parallel import ClientPopulation, FederationEngine
        from tpfl.parallel.crosshost import launch

        ch: dict = {}
        R = 4
        ref = launch(
            num_processes=1, devices_per_proc=8, rounds=R,
            knobs={"SHARD_NODES": True, "SHARD_HOSTS": 1,
                   "ENGINE_TELEMETRY": False},
        )[0]
        dense = launch(
            num_processes=2, devices_per_proc=4, rounds=R,
            knobs={"SHARD_NODES": True, "SHARD_HOSTS": 0,
                   "ENGINE_TELEMETRY": False,
                   "ENGINE_WIRE_CODEC": "dense"},
        )
        q8 = launch(
            num_processes=2, devices_per_proc=4, rounds=R,
            knobs={"SHARD_NODES": True, "SHARD_HOSTS": 0,
                   "ENGINE_TELEMETRY": False,
                   "ENGINE_WIRE_CODEC": "quant8"},
        )[0]
        ch["mesh"] = dense[0]["mesh"]
        ch["processes"] = dense[0]["processes"]
        ch["parity_allclose"] = bool(
            np.allclose(
                np.array(dense[0]["global"]), np.array(ref["global"]),
                atol=1e-5,
            )
        )
        ch["ranks_byte_identical"] = (
            dense[0]["digest"] == dense[1]["digest"]
        )
        ch["dcn_bytes_per_round_dense"] = dense[0]["dcn_bytes_per_round"]
        ch["dcn_bytes_per_round_quant8"] = q8["dcn_bytes_per_round"]
        ch["dcn_bytes_ratio"] = round(
            dense[0]["dcn_bytes_per_round"]
            / max(q8["dcn_bytes_per_round"], 1),
            3,
        )
        ld, lq = dense[0]["loss_mean"], q8["loss_mean"]
        ch["dcn_loss_within_2pct"] = bool(
            abs(lq - ld) / max(abs(ld), 1e-9) <= 0.02
        )

        # --- sim1m: the cross-device population tier -----------------
        popl, K, R_pop = 1_000_000, 100, 3
        eng = FederationEngine(
            MLP(hidden_sizes=(16,)), K, mesh=None, seed=0,
            learning_rate=0.1,
        )
        pop = ClientPopulation(registered=popl, sample=K, seed=0)
        eng.attach_population(pop)
        ck = EngineCheckpointer(
            tempfile.mkdtemp(prefix="tpfl_crosshost_ck_")
        )
        glob = jax.tree_util.tree_map(
            lambda leaf: np.asarray(leaf[0]),
            eng.unpad(eng.init_params((8, 8))),
        )
        bpm = compression.wire_bytes_per_model(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), glob
            ),
            0,
        )
        rng = np.random.default_rng(0)
        xs_k = rng.random((K, 1, 16, 8, 8), np.float32)
        ys_k = rng.integers(0, 10, (K, 1, 16)).astype(np.int32)

        def one_round():
            ids = pop.begin_round()
            w = pop.round_weights(ids, cutoff_frac=0.1)
            p = eng.pad_stacked(eng.broadcast_params(glob))
            dx, dy = eng.shard_data(xs_k, ys_k)
            p, losses = eng.run_rounds(p, dx, dy, weights=w, donate=False)
            pop.complete_round(ids, w, np.asarray(losses)[:K])
            ck.save(eng.export_state(p), step=pop.round)
            return jax.tree_util.tree_map(
                lambda leaf: np.asarray(leaf[0]), eng.unpad(p)
            )

        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        glob = one_round()  # warmup (compile + first checkpoint)
        t0 = time.monotonic()
        for _ in range(R_pop):
            glob = one_round()
        wall = time.monotonic() - t0
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        state, meta = ck.restore()
        eng2 = FederationEngine(
            MLP(hidden_sizes=(16,)), K, mesh=None, seed=0,
            learning_rate=0.1,
        )
        eng2.import_state(state)
        delta_mb = max(0.0, (rss1 - rss0) / 1024.0)
        ch["sim1m"] = {
            "registered": popl,
            "sampled": K,
            "rounds": R_pop,
            "rounds_per_sec": round(R_pop / max(wall, 1e-9), 2),
            "exchange_bytes_per_round": int(K * bpm),
            "touched": pop.touched,
            # O(census) records at 1M would be hundreds of MB; the
            # sampled tier must stay in tens.
            "rss_delta_mb": round(delta_mb, 1),
            "rss_bounded": bool(delta_mb < 256.0),
            "ckpt_roundtrip_exact": bool(
                eng2.population is not None
                and eng2.population.clients == pop.clients
                and eng2.population.round == pop.round
            ),
        }
        extra["crosshost"] = ch
    except Exception as e:
        extra["crosshost_error"] = str(e)[:300]


def _fleetobs_tier(extra: dict) -> None:
    """Fleet observatory tier (ISSUE 20). Four receipts, all CPU-safe:

    - extra.fleetobs determinism: two same-seed 2-process
      ``jax.distributed`` launches under ENGINE_TELEMETRY; folding
      each run's worker receipts (``fleetobs.fold_receipts``) must
      yield ONE fleet registry with ``origin=<rank>`` labels whose
      Prometheus rendering is byte-identical across the runs.
    - extra.fleetobs watchdog: a deterministically-driven SLO
      watchdog (injectable ``now=``) must flag a ~20% rounds/sec
      regression within 2 evaluation windows, while the uninjected
      same-length A run stays silent — the alert fires on real
      regressions and ONLY on real regressions.
    - extra.fleetobs overhead: the observatory's per-round cost
      (population fan-out + fleet gauges + snapshot publish + one
      watchdog window) measured INSIDE a live sampled-population
      round loop must stay <= 5% of the round wall clock.
    - extra.fleetobs pop_sketch: the census sweep 100k -> 1M with
      K=100 must hold a bounded peak-RSS delta, and the coverage
      bitset must cost EXACTLY (census+7)//8 bytes — the one
      O(census) concession, priced in bits.
    """
    try:
        import resource
        import tempfile

        import numpy as np

        from tpfl.management import fleetobs
        from tpfl.management.telemetry import MetricsRegistry
        from tpfl.parallel import ClientPopulation
        from tpfl.parallel.crosshost import launch

        fo: dict = {}

        # --- merged-view determinism across same-seed launches -------
        texts = []
        for _ in range(2):
            res = launch(
                num_processes=2, devices_per_proc=4, rounds=2,
                knobs={"SHARD_NODES": True, "SHARD_HOSTS": 0,
                       "ENGINE_TELEMETRY": True},
            )
            texts.append(
                fleetobs.fold_receipts(res).render_prometheus()
            )
        fo["origin_labels_present"] = bool(
            'origin="0"' in texts[0] and 'origin="1"' in texts[0]
        )
        fo["merged_byte_identical"] = bool(texts[0] == texts[1])

        # --- watchdog catch: injected regression vs silent A run -----
        def drive(rates):
            reg = MetricsRegistry()
            wd = fleetobs.SLOWatchdog(
                "rate(tpfl_engine_rounds_total) >= 2.4", registry=reg,
                node="bench-watchdog",
            )
            wd.evaluate(now=0.0)  # warm the rate state
            t, windows_after_injection = 0.0, None
            for i, rate in enumerate(rates):
                t += 1.0
                reg.counter("tpfl_engine_rounds_total", rate)
                wd.evaluate(now=t)
                if rate < 2.4 and windows_after_injection is None:
                    windows_after_injection = 0
                if windows_after_injection is not None:
                    windows_after_injection += 1
                    if not wd.healthy():
                        return windows_after_injection
            return None  # never breached

        healthy = [2.5] * 8
        injected = [2.5] * 4 + [2.0] * 6  # ~20% rounds/sec regression
        fo["uninjected_silent"] = bool(drive(healthy) is None)
        caught = drive(injected)
        fo["windows_to_breach"] = caught
        fo["watchdog_catch_within_2"] = bool(
            caught is not None and caught <= 2
        )

        # --- observatory overhead on a live engine round loop --------
        # A/B the SAME sampled-population federation round with and
        # without the fleet plane (population fan-out + fleet gauges
        # + snapshot publish + one watchdog window); median per-round
        # time keeps one scheduler hiccup from deciding the gate.
        from tpfl.models import MLP
        from tpfl.parallel import FederationEngine

        import jax

        K, R_obs = 64, 10
        eng = FederationEngine(
            MLP(hidden_sizes=(256, 256)), K, mesh=None, seed=0,
            learning_rate=0.1,
        )
        pop = ClientPopulation(registered=100_000, sample=K, seed=0)
        eng.attach_population(pop)
        pub = fleetobs.FleetPublisher(
            "bench", directory=tempfile.mkdtemp(prefix="tpfl_fleetobs_"),
        )
        wd = fleetobs.SLOWatchdog(
            "rate(tpfl_pop_folded_total) >= 0.0", node="bench-overhead"
        )
        rng = np.random.default_rng(0)
        xs_k = rng.random((K, 1, 64, 8, 8), np.float32)
        ys_k = rng.integers(0, 10, (K, 1, 64)).astype(np.int32)
        p = eng.init_params((8, 8))
        dx, dy = eng.shard_data(xs_k, ys_k)

        def one_round(fleet_plane, r=0):
            nonlocal p
            ids = pop.begin_round()
            w = pop.round_weights(ids, cutoff_frac=0.1)
            p, _ = eng.run_rounds(p, dx, dy, weights=w, donate=False)
            # Block: the A/B prices the observatory against a REAL
            # round, not against JAX's async dispatch returning early.
            jax.block_until_ready(p)
            pop.complete_round(ids, w)
            if fleet_plane:
                fleetobs.emit_fleet_gauges("bench")
                wd.evaluate()
                if r % 10 == 0:
                    # The deployed publisher is PERIODIC
                    # (FLEETOBS_SNAPSHOT_PERIOD), not per-round —
                    # amortize one snapshot write per 10 rounds.
                    pub.publish_once()

        def median_round_s(fleet_plane):
            times = []
            for r in range(R_obs):
                t0 = time.monotonic()
                one_round(fleet_plane, r=r + 1)
                times.append(time.monotonic() - t0)
            return sorted(times)[len(times) // 2]

        one_round(True)  # warmup: compile + first publish
        base_s = median_round_s(False)
        fleet_s = median_round_s(True)
        overhead = max(0.0, fleet_s - base_s) / max(base_s, 1e-9)
        fo["rounds_per_sec"] = round(1.0 / max(fleet_s, 1e-9), 2)
        fo["overhead_frac"] = round(overhead, 4)
        fo["overhead_within_budget"] = bool(overhead <= 0.05)

        # --- population sketches: bounded RSS on the census sweep ----
        def sweep(census):
            p = ClientPopulation(registered=census, sample=100, seed=5)
            for _ in range(3):
                ids = p.begin_round()
                p.complete_round(ids, p.round_weights(ids, 0.1))
            return p

        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        small = sweep(100_000)
        big = sweep(1_000_000)
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        delta_mb = max(0.0, (rss1 - rss0) / 1024.0)
        fo["pop_sketch"] = {
            "census_sweep": [100_000, 1_000_000],
            "rss_delta_mb": round(delta_mb, 1),
            # O(census) records would cost hundreds of MB at 1M; the
            # sketches are a bitset + O(touched) dicts.
            "rss_bounded": bool(delta_mb < 64.0),
            "bitset_bytes_exact": bool(
                small._coverage.nbytes == (100_000 + 7) // 8
                and big._coverage.nbytes == (1_000_000 + 7) // 8
            ),
            "coverage_1m": round(big.coverage, 6),
            "fairness_1m": round(big.fairness, 6),
        }
        extra["fleetobs"] = fo
    except Exception as e:
        extra["fleetobs_error"] = str(e)[:300]


#: Named tiers ``--tiers`` selects from. The device tiers need a real
#: accelerator to mean anything; the rest are CPU-safe (the CI
#: perf-smoke job runs ``--tiers profiling --check ...``).
TIERS = (
    "primary", "resnet", "attention", "transformer", "sim1000",
    "multichip", "wire", "serde", "chaos", "analysis", "telemetry",
    "profiling", "ledger", "byzantine", "async", "engine_obs",
    "engine_wire", "engine_async", "elastic", "transformer_fed",
    "crosshost", "fleetobs",
)


def _parse_tiers(spec: str) -> set[str]:
    if spec.strip() == "all":
        return set(TIERS)
    tiers = {t.strip() for t in spec.split(",") if t.strip()}
    unknown = tiers - set(TIERS)
    if unknown:
        raise SystemExit(
            f"unknown tier(s) {sorted(unknown)}; known: all, {', '.join(TIERS)}"
        )
    return tiers


def _tier_errors(extra: dict) -> list[str]:
    """The ``*_error`` keys a run's tiers recorded (sorted) — every tier
    wraps its body so one failure cannot cost the others their numbers,
    which also means a failed tier looks like a run: ``main`` exits
    non-zero when this is non-empty."""
    return sorted(k for k in extra if k.endswith("_error"))


def _check_verdict(doc: dict, baseline_path: str) -> int:
    """Run the perf regression gate over a bench result document:
    attaches the machine-readable verdict as ``extra.check``, prints
    each regression to stderr, returns the process exit code (0 pass,
    1 fail)."""
    import sys as _sys

    from tpfl.management.profiling import compare_to_baseline

    with open(baseline_path, encoding="utf-8") as f:
        baseline = json.load(f)
    verdict = compare_to_baseline(doc, baseline)
    doc.setdefault("extra", {})["check"] = verdict
    for entry in verdict["checked"]:
        if not entry.get("ok", True):
            print(
                f"PERF REGRESSION: {entry['metric']} ({entry.get('path')}) "
                f"= {entry.get('value')} vs baseline {entry.get('baseline')} "
                f"(ratio {entry.get('ratio')}, {entry.get('direction')}-is-"
                f"better within {entry.get('tolerance')})",
                file=_sys.stderr,
            )
    return 0 if verdict["pass"] else 1


def _profiling_tier(extra: dict) -> None:
    """Device-plane observatory tier (management/profiling). Three
    reports:

    - extra.profiling_compile: CompileObservatory mechanics on a
      shape-churn probe — distinct-signature (= recompilation) counting
      and the storm detection threshold firing.
    - extra.profiling_ab: the same seeded 4-node digits federation run
      with PROFILING_ENABLED off and on — the profiled run must cost
      <5% rounds/sec (the DISABLED path adds zero dispatches by
      construction; this measures the enabled tax), and its per-round
      attribution (train/dispatch/fold/gossip/host_other) must cover
      >=95% of every round's wall-clock.
    - extra.profiling_mfu: the live MFU gauge (CostModel.record_round,
      fed by the primary tier) vs the primary tier's analytic MFU
      column — one accounting path, so they must agree within 5%
      whenever the primary tier ran on a device with a known peak.
    """
    from tpfl.management import profiling
    from tpfl.settings import Settings

    try:
        # (a) Observatory mechanics on a shape-churn probe.
        import jax
        import jax.numpy as jnp

        snap_enabled = Settings.PROFILING_ENABLED
        snap_warn = Settings.PROFILING_RECOMPILE_WARN
        try:
            Settings.PROFILING_ENABLED = True
            Settings.PROFILING_RECOMPILE_WARN = 3
            profiling.observatory.reset()

            @jax.jit
            def probe(x):
                return (x * 2.0).sum()

            wrapped = profiling.observatory.wrap(probe, "bench_probe")
            wrapped(jnp.zeros((8,), jnp.float32))
            wrapped(jnp.zeros((8,), jnp.float32))  # signature hit
            for n in (16, 32, 64):  # shape churn: three more compiles
                wrapped(jnp.zeros((n,), jnp.float32))
            sigs = profiling.observatory.signature_counts().get(
                "bench_probe", 0
            )
            extra["profiling_compile"] = {
                "probe_signatures": sigs,
                "storm_detected": bool(sigs >= 3),
            }
        finally:
            Settings.PROFILING_ENABLED = snap_enabled
            Settings.PROFILING_RECOMPILE_WARN = snap_warn
            profiling.observatory.reset()

        # (b) Overhead A/B + per-round attribution coverage.
        snap = Settings.snapshot()
        try:
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            Settings.ELECTION = "hash"  # n <= TRAIN_SET_SIZE: all elected
            Settings.SEED = 2626

            def run(profiled: bool, tag: str) -> dict:
                from tpfl.learning.dataset import (
                    RandomIIDPartitionStrategy,
                    synthetic_mnist,
                )
                from tpfl.models import create_model
                from tpfl.node import Node
                from tpfl.utils import wait_convergence, wait_to_finish

                Settings.PROFILING_ENABLED = profiled
                profiling.rounds.reset()
                n, rounds = 4, 5
                ds = synthetic_mnist(
                    n_train=150 * n, n_test=30, seed=0, noise=0.6
                )
                parts = ds.generate_partitions(
                    n, RandomIIDPartitionStrategy, seed=1
                )
                nodes = [
                    Node(
                        create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,)),
                        parts[i],
                        addr=f"{tag}-{i}",  # pinned: seeded data order
                        learning_rate=0.05,
                        batch_size=32,
                    )
                    for i in range(n)
                ]
                for nd in nodes:
                    nd.start()
                try:
                    for nd in nodes[1:]:
                        nodes[0].connect(nd.addr)
                    wait_convergence(nodes, n - 1, only_direct=False, wait=10)
                    t0 = time.monotonic()
                    nodes[0].set_start_learning(rounds=rounds, epochs=1)
                    wait_to_finish(nodes, timeout=240)
                    elapsed = time.monotonic() - t0
                finally:
                    for nd in nodes:
                        nd.stop()
                out = {
                    "rounds": rounds,
                    "elapsed_s": round(elapsed, 2),
                    "rounds_per_s": round(rounds / elapsed, 3),
                }
                if profiled:
                    out["attribution"] = profiling.rounds.attribution()
                return out

            run(False, "prof-warm")  # discarded: pays the jit warmup
            off = run(False, "prof-off")
            on = run(True, "prof-on")
            overhead = 1.0 - on["rounds_per_s"] / max(off["rounds_per_s"], 1e-9)
            recs = on.pop("attribution")
            wall_total = max(sum(r["wall"] for r in recs), 1e-9)
            comps = {
                c: round(
                    sum(r["parts"].get(c, 0.0) for r in recs) / wall_total, 4
                )
                for c in profiling.COMPONENTS
            }
            coverage_min = min((r["coverage"] for r in recs), default=0.0)
            extra["profiling_ab"] = {
                "seed": 2626,
                "unprofiled": off,
                "profiled": on,
                "overhead_frac": round(overhead, 4),
                "within_5pct_budget": bool(overhead < 0.05),
                "rounds_attributed": len(recs),
                "component_fracs": comps,
                "coverage_min": round(coverage_min, 4),
                "coverage_ge_95pct": bool(recs and coverage_min >= 0.95),
            }
        finally:
            Settings.restore(snap)
            profiling.rounds.reset()

        # (c) Live vs analytic MFU: both columns come from the one
        # CostModel path now, so a disagreement means the timing —
        # not the flops — diverged.
        live = extra.get("profiling_live_mfu")
        analytic = extra.get("mfu")
        if live is not None and analytic:
            rel = abs(live - analytic) / max(abs(analytic), 1e-12)
            extra["profiling_mfu"] = {
                "analytic_mfu": analytic,
                "live_mfu": live,
                "rel_diff": round(rel, 4),
                "within_5pct": bool(rel <= 0.05),
            }
    except Exception as e:
        extra["profiling_error"] = str(e)[:200]


def _ledger_tier(extra: dict) -> None:
    """Learning-plane observatory tier (management/ledger). Three
    reports:

    - extra.ledger_detection: seeded 10-node digits federation at 20%
      sign-flip + 20% additive-noise adversaries — AnomalyScorer
      precision/recall against the harness's known adversary map
      (attacks/harness ground truth; acceptance: both >= 0.9) from the
      deterministic detections() view.
    - extra.ledger_determinism: two same-seed detection runs must
      produce byte-identical flag sets (the detection surface is a
      pure function of seed-deterministic features).
    - extra.ledger_ab: rounds/sec with the ledger off vs on, at the
      4-node fault-free scale every observability tier measures its
      tax — the DISABLED path adds zero dispatches by construction;
      the enabled tax must stay within the shared 5% budget.
    """
    from tpfl.management import ledger
    from tpfl.settings import Settings

    try:
        snap = Settings.snapshot()
        try:
            from tpfl.attacks import (
                additive_noise,
                adversary_map,
                run_seeded_experiment,
                sign_flip,
            )
            from tpfl.management import ledger as _ledger
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            seed = 4242
            # Everyone trains every round (hash election with
            # candidates <= K elects all): every contribution enters
            # every open aggregator, so the ledger sees the full
            # population each round.
            Settings.ELECTION = "hash"

            # 20% sign-flip + 20% additive-noise over 10 nodes (one
            # attack instance per adversary — the noise counter is
            # closure state).
            def adversaries():
                return {
                    1: sign_flip(),
                    4: sign_flip(),
                    6: additive_noise(0.1, seed=6),
                    8: additive_noise(0.1, seed=8),
                }

            def run_detect() -> "tuple[dict, str]":
                Settings.LEDGER_ENABLED = True
                Settings.TRAIN_SET_SIZE = 10
                ledger.contrib.reset()
                ledger.convergence.reset()
                exp = run_seeded_experiment(
                    seed, 10, 2,
                    adversaries=adversaries(),
                    samples_per_node=60,
                    batch_size=20,
                    timeout=240.0,
                )
                return ledger.contrib.detections(), exp

            def run_ab(ledger_on: bool) -> float:
                # Overhead arm at the scale every observability tier
                # measures its tax (4 nodes, fault-free), with enough
                # rounds that the fixed setup (start/connect/init
                # diffusion) amortizes out of the rounds/sec figure.
                Settings.LEDGER_ENABLED = ledger_on
                Settings.TRAIN_SET_SIZE = 4
                ledger.contrib.reset()
                ledger.convergence.reset()
                t0 = time.monotonic()
                run_seeded_experiment(
                    2626, 4, 6,
                    samples_per_node=60,
                    batch_size=20,
                    timeout=240.0,
                )
                return time.monotonic() - t0

            # Discarded warm runs pay the training programs' jit warmup
            # AND the ledger's own stat-fn compiles, so the A/B
            # measures steady-state tax, not one-time compilation. The
            # arms INTERLEAVE and take best-of-3: round wall-clock at
            # this scale is protocol-wait quantized (gossip ticks,
            # heartbeat settles) with run noise far above the overhead
            # being measured — min-of-runs with alternating arms
            # cancels both the noise and any host drift.
            det1, exp1 = run_detect()
            det2, _ = run_detect()
            run_ab(True)  # warm (ledger fns compile here)
            off_times, on_times = [], []
            for _ in range(3):
                off_times.append(run_ab(False))
                on_times.append(run_ab(True))
            off_elapsed = min(off_times)
            on_elapsed = min(on_times)
            ab_rounds = 6

            truth = set(adversary_map(exp1))
            flagged = set(det1.get("flagged", {}))
            tp = len(flagged & truth)
            precision = tp / len(flagged) if flagged else 0.0
            recall = tp / len(truth) if truth else 1.0
            extra["ledger_detection"] = {
                "seed": seed,
                "nodes": 10,
                "rounds": 2,
                "adversaries": sorted(truth),
                "flagged": {
                    k: v["reasons"] for k, v in det1["flagged"].items()
                },
                "entries_scored": len(det1["entries"]),
                "precision": round(precision, 4),
                "recall": round(recall, 4),
                "precision_ge_09": bool(precision >= 0.9),
                "recall_ge_09": bool(recall >= 0.9),
            }

            def flag_surface(det: dict) -> str:
                return json.dumps(
                    [
                        {
                            "peer": e["peer"],
                            "round": e["round"],
                            "flagged": e["flagged"],
                            "reasons": e["reasons"],
                        }
                        for e in det.get("entries", [])
                    ],
                    sort_keys=True,
                )

            extra["ledger_determinism"] = {
                "byte_identical_flags": bool(
                    flag_surface(det1) == flag_surface(det2)
                ),
                "entries_run1": len(det1.get("entries", [])),
                "entries_run2": len(det2.get("entries", [])),
            }

            off_rps = ab_rounds / max(off_elapsed, 1e-9)
            on_rps = ab_rounds / max(on_elapsed, 1e-9)
            overhead = 1.0 - on_rps / max(off_rps, 1e-9)
            extra["ledger_ab"] = {
                "unledgered": {
                    "elapsed_s": round(off_elapsed, 2),
                    "rounds_per_s": round(off_rps, 3),
                },
                "ledgered": {
                    "elapsed_s": round(on_elapsed, 2),
                    "rounds_per_s": round(on_rps, 3),
                },
                "overhead_frac": round(overhead, 4),
                "within_5pct_budget": bool(overhead < 0.05),
            }
        finally:
            Settings.restore(snap)
            ledger.contrib.reset()
            ledger.convergence.reset()
    except Exception as e:
        extra["ledger_error"] = str(e)[:200]


def _engine_obs_tier(extra: dict) -> None:
    """Engine-plane telemetry tier (the ENGINE_TELEMETRY carry +
    management/engine_obs fan-out). Three reports:

    - extra.engine_obs_program: the program-split mechanics —
      ``ENGINE_TELEMETRY=False`` lowers a STABLE HLO digest across a
      telemetry toggle (the carry is elided, not masked; the
      program-cache key splits), ``=True`` lowers a different program,
      and same-seed ``run_rounds`` model bytes agree off-vs-on (the
      carry is read-only).
    - extra.engine_obs_detection: a seeded sign-flip AttackPlan lowered
      INTO the fused program (``plan.engine_scales`` →
      ``run_rounds(attack_scales=...)``) — the ledger's deterministic
      ``detections()`` view and the quarantine replay scored against
      the plan's ground truth (acceptance: precision = recall = 1.0 and
      an exact quarantine-set match).
    - extra.engine_obs_ab: windowed ``run_rounds`` rounds/sec with the
      carry off vs on (fan-out registry-only — the other planes stay
      off, as in a production scrape) — the enabled tax must stay
      within the shared 5% budget. Arms interleave, best-of-3, warm
      runs discarded (the observability-tier discipline). The A/B
      round carries a REPRESENTATIVE local-fit load (2000
      samples/node/round): the carry's cost is per-parameter, not
      per-sample, so a degenerate 16-sample round would measure the
      carry against a round that exists nowhere (real CNN rounds are
      heavier still — the measured tax is an upper bound).
    """
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpfl.attacks.plan import AttackPlan, AttackSpec
    from tpfl.management import engine_obs, ledger, quarantine
    from tpfl.models import MLP
    from tpfl.parallel import FederationEngine
    from tpfl.settings import Settings

    try:
        snap = Settings.snapshot()
        try:
            Settings.set_test_settings()
            # Let CI env overrides (TPFL_TELEMETRY_DUMP_DIR — the
            # flight-dump artifact on failure) back through the
            # profile reset.
            Settings.from_env()
            nE, nbE, bsE = 32, 1, 16
            hidden = (64,)
            rngE = np.random.default_rng(7)
            xsE = rngE.random((nE, nbE, bsE, 28, 28), np.float32)
            ysE = rngE.integers(0, 10, (nE, nbE, bsE)).astype(np.int32)

            def engine():
                return FederationEngine(
                    MLP(hidden_sizes=hidden), nE, mesh=None,
                    learning_rate=0.1, seed=0,
                )

            # (a) Program split + byte determinism.
            def hlo_digest(eng, tele):
                fn = eng.program(
                    "plain", 1, 2, 1, donate=False, telemetry=tele
                )
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsE, ysE)
                low = fn.lower(
                    p, {}, {}, {}, xs_d, ys_d,
                    eng.pad_weights(None), eng.valid,
                )
                return hashlib.sha256(low.as_text().encode()).hexdigest()

            e1 = engine()
            off1 = hlo_digest(e1, False)
            on_d = hlo_digest(e1, True)
            off2 = hlo_digest(engine(), False)

            def model_bytes(tele):
                Settings.ENGINE_TELEMETRY = tele
                eng = engine()
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsE, ysE)
                p, _ = eng.run_rounds(p, xs_d, ys_d, n_rounds=3)
                return b"".join(
                    np.asarray(leaf).tobytes()
                    for leaf in jax.tree_util.tree_leaves(p)
                )

            extra["engine_obs_program"] = {
                "off_hlo_identical": bool(off1 == off2),
                "carry_changes_program": bool(on_d != off1),
                "model_bytes_identical": bool(
                    model_bytes(False) == model_bytes(True)
                ),
            }

            # (b) Seeded engine-tier sign-flip adversary through the
            # ledger/quarantine, from the carry alone.
            Settings.ENGINE_TELEMETRY = True
            Settings.LEDGER_ENABLED = True
            ledger.contrib.reset()
            ledger.convergence.reset()
            plan = AttackPlan(
                {3: AttackSpec("sign_flip"), 11: AttackSpec("sign_flip")},
                seed=7,
            )
            addrs = engine_obs.peer_names(nE)
            scales = plan.engine_scales(addrs, n_rounds=4)
            engD = engine()
            pD = engD.init_params((28, 28))
            xs_d, ys_d = engD.shard_data(xsE, ysE)
            engD.run_rounds(pD, xs_d, ys_d, n_rounds=4, attack_scales=scales)
            det = ledger.contrib.detections()
            truth = set(plan.adversary_map(addrs))
            flagged = set(det.get("flagged", {}))
            tp = len(flagged & truth)
            quarantined = quarantine.quarantined_from_replay(
                quarantine.replay_decisions(det)
            )
            extra["engine_obs_detection"] = {
                "nodes": nE,
                "rounds": 4,
                "adversaries": sorted(truth),
                "flagged": sorted(flagged),
                "entries_scored": len(det.get("entries", [])),
                "precision": round(tp / len(flagged), 4) if flagged else 0.0,
                "recall": round(tp / len(truth), 4) if truth else 1.0,
                "quarantine_exact": bool(quarantined == truth),
            }
            ledger.contrib.reset()
            ledger.convergence.reset()
            Settings.LEDGER_ENABLED = False

            # (c) Off/on overhead A/B over windowed run_rounds
            # (registry-only fan-out — the production-scrape shape).
            # Both arms consume each window's losses (the
            # FederationLearner shape: a window's result gates the next
            # protocol round), so the A/B measures the carry + fan-out
            # tax, not a pipelining difference.
            bs_ab, ep_ab, R_ab = 500, 4, 4
            xsA = rngE.random((nE, nbE, bs_ab, 28, 28), np.float32)
            ysA = rngE.integers(0, 10, (nE, nbE, bs_ab)).astype(np.int32)
            # ONE engine per arm, reused across measured runs — a fresh
            # engine per run would pay the jit compile inside the timed
            # region (and the telemetry program compiles slower, which
            # would bill compile time as round overhead).
            arms = {}
            for tele in (False, True):
                eng = engine()
                arms[tele] = (
                    eng,
                    eng.init_params((28, 28)),
                    *eng.shard_data(xsA, ysA),
                )

            def window_elapsed(tele: bool) -> float:
                Settings.ENGINE_TELEMETRY = tele
                eng, p, xs_d, ys_d = arms[tele]
                t0 = time.monotonic()
                for _ in range(2):
                    p, losses = eng.run_rounds(
                        p, xs_d, ys_d, n_rounds=R_ab, epochs=ep_ab,
                        donate=False,
                    )
                    jax.block_until_ready(losses)
                return time.monotonic() - t0

            window_elapsed(False)  # warm: both arms' programs compile
            window_elapsed(True)
            off_times, on_times = [], []
            for _ in range(3):
                off_times.append(window_elapsed(False))
                on_times.append(window_elapsed(True))
            ab_rounds = 2 * R_ab
            off_rps = ab_rounds / max(min(off_times), 1e-9)
            on_rps = ab_rounds / max(min(on_times), 1e-9)
            overhead = 1.0 - on_rps / max(off_rps, 1e-9)
            extra["engine_obs_ab"] = {
                "untelemetered": {
                    "elapsed_s": round(min(off_times), 3),
                    "rounds_per_s": round(off_rps, 2),
                },
                "telemetered": {
                    "elapsed_s": round(min(on_times), 3),
                    "rounds_per_s": round(on_rps, 2),
                },
                "rounds_per_dispatch": R_ab,
                "samples_per_node_round": nbE * bs_ab * ep_ab,
                "overhead_frac": round(overhead, 4),
                "within_5pct_budget": bool(overhead < 0.05),
            }
        finally:
            Settings.restore(snap)
            ledger.contrib.reset()
            ledger.convergence.reset()
    except Exception as e:
        extra["engine_obs_error"] = str(e)[:200]


def _engine_wire_tier(extra: dict) -> None:
    """Device-side wire codec + donation tier (ENGINE_WIRE_CODEC /
    ENGINE_DONATE over the fused engine). Three reports:

    - extra.engine_wire_program: cache-key/lowering mechanics —
      ``ENGINE_WIRE_CODEC="dense"`` lowers a STABLE HLO digest across
      a codec toggle (the codec is elided at trace time, not masked;
      the program-cache key splits on it), "quant8" lowers a
      different program, the DONATING program's same-seed outputs are
      byte-identical to ``donate=False``, and the compiled-HLO
      donation inspection (``FederationEngine.donation_report``) is
      CLEAN: every donated state leaf carries a lowering alias marker
      AND an ``input_output_alias`` pair in the compiled executable —
      the fused train+fold writes its outputs into the buffers it was
      handed, no staging copy.
    - extra.engine_wire_bytes: the bytes/round accounting, read from
      the DEVICE-side telemetry carry (``wire_bytes`` row =
      participation x the codec's per-model tensor bytes, same
      per-leaf policy as the host payload path): dense vs quant8
      per-round exchange bytes and their ratio — gate >= 3x fewer
      (f32 models sit at ~3.99x; envelope overhead is a host concept
      and excluded on both sides).
    - extra.engine_wire_parity: seeded windowed A/B at the
      engine_obs-tier scale — the identical federation run dense vs
      quant8; the quantized steady loss must sit within the 2% gate
      (int8 symmetric quantization on converging updates is
      sub-percent in practice).
    """
    import jax
    import numpy as np

    from tpfl.learning import compression
    from tpfl.management.telemetry import metrics
    from tpfl.models import MLP
    from tpfl.parallel import FederationEngine
    from tpfl.settings import Settings

    try:
        snap = Settings.snapshot()
        try:
            Settings.set_test_settings()
            Settings.from_env()
            nW, nbW, bsW = 32, 1, 64
            rngW = np.random.default_rng(13)
            xsW = rngW.random((nW, nbW, bsW, 28, 28), np.float32)
            ysW = rngW.integers(0, 10, (nW, nbW, bsW)).astype(np.int32)

            def engine():
                return FederationEngine(
                    MLP(hidden_sizes=(64,)), nW, mesh=None,
                    learning_rate=0.1, seed=0,
                )

            # (a) Codec cache-key split + donation mechanics.
            import hashlib

            def hlo_digest(eng, codec):
                bits = compression.resolve_engine_codec(codec)
                fn = eng.program("plain", 1, 2, 1, donate=False, codec=bits)
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsW, ysW)
                low = fn.lower(
                    p, {}, {}, {}, xs_d, ys_d,
                    eng.pad_weights(None), eng.valid,
                )
                return hashlib.sha256(low.as_text().encode()).hexdigest()

            e1 = engine()
            off1 = hlo_digest(e1, "dense")
            on_q = hlo_digest(e1, "quant8")
            e2 = engine()
            hlo_digest(e2, "quant8")  # codec compiled FIRST
            off2 = hlo_digest(e2, "dense")

            def model_bytes(donate):
                Settings.ENGINE_WIRE_CODEC = "dense"
                eng = engine()
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsW, ysW)
                p, _ = eng.run_rounds(p, xs_d, ys_d, n_rounds=3, donate=donate)
                return b"".join(
                    np.asarray(leaf).tobytes()
                    for leaf in jax.tree_util.tree_leaves(p)
                )

            engD = engine()
            pD = engD.init_params((28, 28))
            xs_d, ys_d = engD.shard_data(xsW, ysW)
            report = engD.donation_report(pD, xs_d, ys_d, n_rounds=2)
            extra["engine_wire_program"] = {
                "codec_off_hlo_identical": bool(off1 == off2),
                "codec_changes_program": bool(on_q != off1),
                "donate_bytes_identical": bool(
                    model_bytes(True) == model_bytes(False)
                ),
                "donation_clean": bool(report["clean"]),
                "donation_report": report,
            }

            # (b) Device-side bytes/round, dense vs quant8, read back
            # through the telemetry carry -> engine_obs ->
            # tpfl_engine_wire_bytes gauge (the production scrape path).
            def wire_bytes(codec):
                Settings.ENGINE_TELEMETRY = True
                Settings.ENGINE_WIRE_CODEC = codec
                eng = engine()
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsW, ysW)
                eng.run_rounds(p, xs_d, ys_d, n_rounds=2)
                folded = metrics.fold()
                vals = [
                    v
                    for k, v in folded["gauges"].items()
                    if k[0] == "tpfl_engine_wire_bytes"
                ]
                return float(vals[-1]) if vals else 0.0

            dense_b = wire_bytes("dense")
            quant_b = wire_bytes("quant8")
            Settings.ENGINE_TELEMETRY = False
            ratio = dense_b / max(quant_b, 1.0)
            extra["engine_wire_bytes"] = {
                "dense_bytes_per_round": int(dense_b),
                "quant8_bytes_per_round": int(quant_b),
                "ratio": round(ratio, 3),
                "at_least_3x": bool(ratio >= 3.0),
            }

            # (c) Loss parity: the same seeded windowed federation,
            # dense vs quant8 exchange.
            def steady_loss(codec):
                Settings.ENGINE_WIRE_CODEC = codec
                eng = engine()
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsW, ysW)
                p, losses = eng.run_rounds(
                    p, xs_d, ys_d, n_rounds=6, epochs=2
                )
                return float(np.mean(np.asarray(losses)))

            loss_d = steady_loss("dense")
            loss_q = steady_loss("quant8")
            rel = abs(loss_q - loss_d) / max(abs(loss_d), 1e-9)
            extra["engine_wire_parity"] = {
                "dense_loss": round(loss_d, 5),
                "quant8_loss": round(loss_q, 5),
                "rel_delta": round(rel, 5),
                "within_2pct": bool(rel <= 0.02),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["engine_wire_error"] = str(e)[:200]


def _engine_async_tier(extra: dict) -> None:
    """Free-running engine tier (ISSUE 16: WindowPipeline +
    FedBuffSchedule — the Sebulba split). Three reports:

    - extra.engine_async_throughput: the barrier-removal economics on
      the engine's virtual clock. A seeded ``TrainerSpeedPlan`` with a
      10x-slower 20% tail is lowered to a ``FedBuffSchedule``; the
      wall program cost per round is MEASURED for both the sync and
      fedbuff window programs, then composed with the plan's delays:
      a sync round pays the slowest node (max delay + program), a
      fedbuff round ticks at the fastest cadence (min delay +
      program), the unskewed reference pays base delay + sync
      program. Gates: fedbuff holds >= 0.8x the unskewed throughput
      under skew, where sync degrades below 0.5x.
    - extra.engine_async_pipeline: the device-idle gap the pipelined
      driver removes. Both drivers run the same windows with a
      calibrated ~20 ms host leg per window (data staging stand-in);
      the sequential driver blocks, works, then dispatches (gap =
      host leg); the pipeline reports NO gaps since PR 24 (it no
      longer probes ``is_ready`` on its hot loop; device idleness is
      the device trace's, PERF.md), so the gap gate is vacuous and
      what this sub-tier still checks is pipelined bytes ==
      sequential bytes.
    - extra.engine_async_determinism: two same-seed pipelined fedbuff
      runs end byte-identical — in-process at 1 device, and (CPU
      single-device hosts) in an 8-forced-virtual-device subprocess
      like the multichip tier (``TPFL_ENGINE_ASYNC_SUB``).
    """
    import os
    import time

    import jax
    import numpy as np

    from tpfl.communication.faults import TrainerSpeedPlan
    from tpfl.models import MLP
    from tpfl.parallel import (
        FederationEngine,
        FedBuffSchedule,
        WindowPipeline,
        create_mesh,
    )
    from tpfl.settings import Settings

    def tree_bytes(tree):
        return b"".join(
            np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(tree)
        )

    try:
        snap = Settings.snapshot()
        try:
            Settings.set_test_settings()
            Settings.from_env()

            def data(n, nb=1, bs=32, seed=11):
                rng = np.random.default_rng(seed)
                xs = rng.random((n, nb, bs, 28, 28), np.float32)
                ys = rng.integers(0, 10, (n, nb, bs)).astype(np.int32)
                return xs, ys

            def det_run(mesh, n):
                """One pipelined fedbuff run → final model bytes."""
                eng = FederationEngine(
                    MLP(hidden_sizes=(64,)), n, mesh=mesh,
                    learning_rate=0.1, seed=0,
                )
                p = eng.init_params((28, 28))
                dx, dy = eng.shard_data(*data(n))
                sched = FedBuffSchedule.from_periods(
                    [1 + (i % 3) for i in range(n)], 6
                )
                result, done = WindowPipeline(eng).run(
                    p, dx, dy, n_rounds=6, window=2, schedule=sched
                )
                assert done == 6
                return tree_bytes(result[0])

            if os.environ.get("TPFL_ENGINE_ASYNC_SUB"):
                # Subprocess leg: ONLY the 8-virtual-device receipt.
                mesh8 = create_mesh({"nodes": 8})
                extra["engine_async_determinism"] = {
                    "byte_identical_8dev": bool(
                        det_run(mesh8, 8) == det_run(mesh8, 8)
                    ),
                }
                return

            # (a) Virtual-clock throughput: measured program cost per
            # round composed with the speed plan's delays.
            nA = 10
            addrs = [f"engine-node-{i}" for i in range(nA)]
            base_delay, R = 0.05, 16
            plan = TrainerSpeedPlan.skewed(
                addrs, slow_frac=0.2, base_delay=base_delay,
                skew=10.0, seed=7,
            )
            sched = FedBuffSchedule.from_plan(plan, addrs, R)
            xsA, ysA = data(nA)

            def prog_seconds(schedule):
                eng = FederationEngine(
                    MLP(hidden_sizes=(64,)), nA, mesh=None,
                    learning_rate=0.1, seed=0,
                )
                p = eng.init_params((28, 28))
                dx, dy = eng.shard_data(xsA, ysA)
                out, _ = eng.run_rounds(  # warm: compile + first run
                    p, dx, dy, n_rounds=R, donate=False,
                    schedule=schedule,
                )
                jax.block_until_ready(out)
                t0 = time.monotonic()
                out, _ = eng.run_rounds(
                    p, dx, dy, n_rounds=R, donate=False,
                    schedule=schedule,
                )
                jax.block_until_ready(out)
                return (time.monotonic() - t0) / R

            c_sync = prog_seconds(None)
            c_fb = prog_seconds(sched)
            delays = [plan.delay_for(a) for a in addrs]
            tick = min(d for d in delays if d > 0)
            slowest = max(delays)
            unskewed_rps = 1.0 / (base_delay + c_sync)
            sync_rps = 1.0 / (slowest + c_sync)
            fedbuff_rps = 1.0 / (tick + c_fb)
            fb_vs_unskewed = fedbuff_rps / unskewed_rps
            sync_vs_unskewed = sync_rps / unskewed_rps
            extra["engine_async_throughput"] = {
                "skew": "20% of trainers 10x slower (TrainerSpeedPlan)",
                "program_s_per_round_sync": round(c_sync, 5),
                "program_s_per_round_fedbuff": round(c_fb, 5),
                "virtual_rps_unskewed": round(unskewed_rps, 3),
                "virtual_rps_sync_skewed": round(sync_rps, 3),
                "virtual_rps_fedbuff_skewed": round(fedbuff_rps, 3),
                "fedbuff_vs_unskewed": round(fb_vs_unskewed, 3),
                "sync_vs_unskewed": round(sync_vs_unskewed, 3),
                "fedbuff_holds_0_8x": bool(fb_vs_unskewed >= 0.8),
                "sync_degrades": bool(sync_vs_unskewed <= 0.5),
            }

            # (b) Idle gap: pipelined vs sequential driver, identical
            # windows, ~20 ms calibrated host leg per window.
            HOST_LEG = 0.02
            nP, RP, W = 16, 8, 2
            xsP, ysP = data(nP, nb=2)

            def engineP():
                return FederationEngine(
                    MLP(hidden_sizes=(64,)), nP, mesh=None,
                    learning_rate=0.1, seed=0,
                )

            def staged(widx, start, k):
                time.sleep(HOST_LEG)  # data staging stand-in
                return None

            def run_sequential():
                eng = engineP()
                p = eng.init_params((28, 28))
                dx, dy = eng.shard_data(xsP, ysP)
                gaps, done, t_ready = [], 0, None
                while done < RP:
                    k = min(W, RP - done)
                    staged(done // W, done, k)
                    t_disp = time.monotonic()
                    if t_ready is not None:
                        gaps.append(t_disp - t_ready)
                    handle = eng.dispatch_window(
                        p, dx, dy, n_rounds=k
                    )
                    p = handle.params
                    jax.block_until_ready(p)
                    t_ready = time.monotonic()
                    handle.finalize()
                    done += k
                return tree_bytes(p), gaps

            def run_pipelined():
                eng = engineP()
                p = eng.init_params((28, 28))
                dx, dy = eng.shard_data(xsP, ysP)
                pipe = WindowPipeline(eng)
                result, done = pipe.run(
                    p, dx, dy, n_rounds=RP, window=W,
                    data_for=staged, prefetch=True,
                )
                assert done == RP
                # The pipeline no longer infers device idleness on the
                # host (PERF.md, PR 24: the device trace's
                # device_idle_pct reads it): no gaps to report.
                return tree_bytes(result[0]), []

            run_sequential()  # warm: compile both window shapes
            seq_bytes, seq_gaps = run_sequential()
            pipe_bytes, pipe_gaps = run_pipelined()
            seq_gap = float(np.mean(seq_gaps)) if seq_gaps else 0.0
            pipe_gap = float(np.mean(pipe_gaps)) if pipe_gaps else 0.0
            extra["engine_async_pipeline"] = {
                "host_leg_s_per_window": HOST_LEG,
                "windows": RP // W,
                "seq_idle_gap_s": round(seq_gap, 5),
                "pipeline_idle_gap_s": round(pipe_gap, 5),
                "gap_cut": round(seq_gap / max(pipe_gap, 1e-6), 2),
                "gap_cut_2x": bool(seq_gap >= 2.0 * pipe_gap),
                "bytes_identical": bool(seq_bytes == pipe_bytes),
            }

            # (c) Same-seed pipelined fedbuff determinism.
            det = {"byte_identical_1dev": bool(
                det_run(None, 8) == det_run(None, 8)
            )}
            if jax.device_count() >= 8:
                mesh8 = create_mesh(
                    {"nodes": 8}, devices=jax.devices()[:8]
                )
                det["byte_identical_8dev"] = bool(
                    det_run(mesh8, 8) == det_run(mesh8, 8)
                )
            elif jax.default_backend() == "cpu":
                # Single-device CPU host: force 8 virtual devices in a
                # subprocess (the multichip-tier discipline — flipping
                # XLA_FLAGS process-wide would skew other tiers). One
                # process per chip: reached only when THIS process runs
                # on the CPU, and the child is pinned to the CPU
                # through its environment.
                import json as _json
                import subprocess
                import sys as _sys

                env = dict(
                    os.environ,
                    JAX_PLATFORMS="cpu",
                    TPFL_ENGINE_ASYNC_SUB="1",
                    XLA_FLAGS=(
                        os.environ.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                    ).strip(),
                )
                proc = subprocess.run(
                    [
                        _sys.executable,
                        os.path.abspath(__file__),
                        "--tiers",
                        "engine_async",
                    ],
                    capture_output=True, text=True, env=env,
                    timeout=1200,
                )
                sub = _json.loads(proc.stdout.splitlines()[-1])
                sub_det = sub["extra"].get("engine_async_determinism", {})
                det["byte_identical_8dev"] = bool(
                    sub_det.get("byte_identical_8dev", False)
                )
                det["subprocess_devices"] = 8
            extra["engine_async_determinism"] = det
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["engine_async_error"] = str(e)[:200]


def _elastic_tier(extra: dict) -> None:
    """Elastic engine tier (ISSUE 17: zero-recompile membership churn
    + kill-and-resume checkpointing). Four receipts:

    - extra.elastic_storm: a 20-event join/leave/crash/quarantine/
      readmit storm over 30 engine rounds through a ``MembershipView``.
      Gates: every engine program holds exactly ONE compile signature
      (churn inside a tier is a weight-mask edit — the
      CompileObservatory is the receipt), and the total compile count
      beyond the initial program equals the view's tier promotions
      (recompiles == promotions, nothing else).
    - extra.elastic_masked: an elastic capacity-8 run with 4 live
      members vs a fresh-compiled exact-size n=4 run on the same
      8-device ``nodes`` mesh — live rows byte-identical (the masked
      program IS the exact program over identical inputs). Runs in an
      8-forced-virtual-device subprocess on single-device CPU hosts
      (``TPFL_ELASTIC_SUB``), like the multichip tier.
    - extra.elastic_resume: kill-and-resume equivalence — 3 rounds, an
      ``EngineCheckpointer`` round trip through disk, 3 more rounds on
      a FRESH engine vs 6 uninterrupted: byte-identical, plus the
      sha256 digest of the final model bytes.
    - extra.elastic_snapshot: cadence-checkpoint overhead — the same
      pipelined run with and without ``snapshot_every`` (snapshots ride
      the non-blocking host copy off the dispatch path). Gate: ≤ 5%
      wall overhead.
    """
    import hashlib
    import os
    import tempfile
    import time

    import jax
    import numpy as np

    from tpfl.management import profiling
    from tpfl.management.checkpoint import EngineCheckpointer
    from tpfl.models import MLP
    from tpfl.parallel import (
        FederationEngine,
        WindowPipeline,
        create_mesh,
    )
    from tpfl.parallel.membership import MembershipView
    from tpfl.settings import Settings

    def tree_bytes(tree):
        return b"".join(
            np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(tree)
        )

    def data(n, nb=1, bs=32, seed=13):
        rng = np.random.default_rng(seed)
        xs = rng.random((n, nb, bs, 28, 28), np.float32)
        ys = rng.integers(0, 10, (n, nb, bs)).astype(np.int32)
        return xs, ys

    def engine(n, mesh=None):
        return FederationEngine(
            MLP(hidden_sizes=(64,)), n, mesh=mesh,
            learning_rate=0.1, seed=0,
        )

    def masked_receipt(mesh8):
        """Elastic capacity-8 (4 live) vs exact n=4 on the same mesh:
        both pad to 8 rows (row-0 clones at zero weight), so the
        inputs — and therefore the outputs — are bitwise identical."""
        n_live = 4
        xs, ys = data(n_live)
        exact = engine(n_live, mesh=mesh8)
        p = exact.init_params((28, 28))
        dx, dy = exact.shard_data(xs, ys)
        out_exact, _ = exact.run_rounds(p, dx, dy, n_rounds=2,
                                        donate=False)
        view = MembershipView(
            [f"n{i}" for i in range(n_live)], capacity_min=8
        )
        el = engine(8, mesh=mesh8)
        el.attach_membership(view)

        def pad(a):
            return np.concatenate(
                [a, np.broadcast_to(a[:1], (4, *a.shape[1:]))]
            )

        dx8, dy8 = el.shard_data(pad(xs), pad(ys))
        p8 = el.pad_stacked(exact.unpad(p))
        out_el, _ = el.run_rounds(p8, dx8, dy8, weights=view.weights(),
                                  n_rounds=2, donate=False)

        def live(t):
            return jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:n_live], t
            )

        return bool(tree_bytes(live(out_el)) == tree_bytes(live(out_exact)))

    try:
        snap = Settings.snapshot()
        try:
            Settings.set_test_settings()
            Settings.from_env()

            if os.environ.get("TPFL_ELASTIC_SUB"):
                # Subprocess leg: ONLY the 8-virtual-device masked
                # receipt.
                mesh8 = create_mesh({"nodes": 8})
                extra["elastic_masked"] = {
                    "byte_identical": masked_receipt(mesh8),
                    "devices": 8,
                }
                return

            # (a) Churn storm: 20 membership events over 30 rounds,
            # one engine, the observatory counting every compile.
            events = [
                ("leave", "n1"), ("join", "n1"), ("crash", "n2"),
                ("join", "n2"), ("quarantine", "n3"), ("readmit", "n3"),
                ("leave", "n0"), ("join", "n0"), ("quarantine", "n1"),
                ("readmit", "n1"), ("crash", "n3"), ("join", "n3"),
                ("leave", "n2"), ("join", "n2"), ("quarantine", "n0"),
                ("readmit", "n0"),
                ("join", "n4"),  # slot 5 of 4: the ONE promotion
                ("leave", "n4"), ("join", "n4"), ("quarantine", "n4"),
            ]
            R_STORM = 30
            view = MembershipView(
                [f"n{i}" for i in range(4)], capacity_min=4
            )
            eng = engine(4)
            eng.attach_membership(view)
            p = eng.init_params((28, 28))
            xs_full, ys_full = data(8)
            dx, dy = eng.shard_data(xs_full[:4], ys_full[:4])
            Settings.PROFILING_ENABLED = True
            profiling.observatory.reset()
            for r in range(R_STORM):
                if r < len(events):
                    kind, addr = events[r]
                    getattr(view, kind)(addr)
                u = eng.unpad(p)
                if eng.sync_membership():
                    # Tier boundary: re-pad state/data at the new
                    # capacity — the one churn event that compiles.
                    p = eng.pad_stacked(u)
                    dx, dy = eng.shard_data(
                        xs_full[: eng.n_nodes], ys_full[: eng.n_nodes]
                    )
                p, _ = eng.run_rounds(
                    p, dx, dy, weights=view.weights(), n_rounds=1,
                    donate=False,
                )
            counts = {
                k: v
                for k, v in profiling.observatory.signature_counts().items()
                if k.startswith("engine_round")
            }
            Settings.PROFILING_ENABLED = False
            compiles = int(sum(counts.values()))
            promotions = view.promotions()
            extra["elastic_storm"] = {
                "events": len(events),
                "rounds": R_STORM,
                "programs": counts,
                "promotions": promotions,
                "zero_recompiles": bool(
                    counts and all(v == 1 for v in counts.values())
                ),
                "recompiles_equal_promotions": bool(
                    compiles - 1 == promotions
                ),
                "tier_events": view.tier_events(),
            }

            # (b) Masked-vs-exact byte identity (needs 8 devices for
            # matched padded sizes).
            if jax.device_count() >= 8:
                mesh8 = create_mesh(
                    {"nodes": 8}, devices=jax.devices()[:8]
                )
                extra["elastic_masked"] = {
                    "byte_identical": masked_receipt(mesh8),
                    "devices": 8,
                }
            elif jax.default_backend() == "cpu":
                # Single-device CPU host: force 8 virtual devices in a
                # subprocess (the multichip-tier discipline). One
                # process per chip: CPU parents only, child pinned to
                # the CPU through its environment.
                import json as _json
                import subprocess
                import sys as _sys

                env = dict(
                    os.environ,
                    JAX_PLATFORMS="cpu",
                    TPFL_ELASTIC_SUB="1",
                    XLA_FLAGS=(
                        os.environ.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                    ).strip(),
                )
                proc = subprocess.run(
                    [
                        _sys.executable,
                        os.path.abspath(__file__),
                        "--tiers",
                        "elastic",
                    ],
                    capture_output=True, text=True, env=env,
                    timeout=1200,
                )
                sub = _json.loads(proc.stdout.splitlines()[-1])
                masked = sub["extra"].get("elastic_masked", {})
                extra["elastic_masked"] = {
                    "byte_identical": bool(
                        masked.get("byte_identical", False)
                    ),
                    "devices": 8,
                    "subprocess": True,
                }
            else:
                extra["elastic_masked"] = {
                    "skipped": "needs >= 8 devices for matched padding"
                }

            # (c) Kill-and-resume equivalence digest: 3 + (disk round
            # trip) + 3 rounds on a FRESH engine vs 6 uninterrupted.
            nR = 4
            xsR, ysR = data(nR)
            eng_a = engine(nR)
            pa = eng_a.init_params((28, 28))
            dxa, dya = eng_a.shard_data(xsR, ysR)
            pa, _ = eng_a.run_rounds(pa, dxa, dya, n_rounds=6,
                                     donate=False)
            eng_b = engine(nR)
            pb = eng_b.init_params((28, 28))
            dxb, dyb = eng_b.shard_data(xsR, ysR)
            pb, _ = eng_b.run_rounds(pb, dxb, dyb, n_rounds=3,
                                     donate=False)
            with tempfile.TemporaryDirectory() as td:
                ck = EngineCheckpointer(td, node="bench")
                ck.save(eng_b.export_state(pb), step=3)
                state, meta = ck.restore()
            eng_c = engine(nR)
            out = eng_c.import_state(state)
            dxc, dyc = eng_c.shard_data(xsR, ysR)
            pc, _ = eng_c.run_rounds(out["params"], dxc, dyc,
                                     n_rounds=3, donate=False)
            b_full = tree_bytes(eng_a.unpad(pa))
            b_res = tree_bytes(eng_c.unpad(pc))
            extra["elastic_resume"] = {
                "rounds": 6,
                "resume_at": int(meta["step"]),
                "byte_identical": bool(b_full == b_res),
                "digest": hashlib.sha256(b_full).hexdigest()[:16],
                "resumed_digest": hashlib.sha256(b_res).hexdigest()[:16],
            }

            # (d) Snapshot overhead: same engine, same program, same
            # windows — with vs without the cadence checkpoint.
            nS, RS, WS, EP, EVERY = 16, 24, 2, 8, 4
            # Batches sized so a rep runs seconds, not milliseconds:
            # host-timing jitter and the fixed per-snapshot cost must
            # both be small against the round compute they ride.
            xsS, ysS = data(nS, nb=2, bs=96)
            eng_s = engine(nS)
            p_s = eng_s.init_params((28, 28))
            dxs, dys = eng_s.shard_data(xsS, ysS)

            def run_once(snap_every=0, snap_to=None, drain=None):
                pipe = WindowPipeline(eng_s)
                t0 = time.monotonic()
                result, done = pipe.run(
                    p_s, dxs, dys, epochs=EP, n_rounds=RS, window=WS,
                    donate=False, snapshot_every=snap_every,
                    snapshot_to=snap_to,
                )
                jax.block_until_ready(result[0])
                if drain is not None:
                    drain()  # published-to-disk before the clock stops
                assert done == RS
                return time.monotonic() - t0

            from concurrent.futures import ThreadPoolExecutor

            with tempfile.TemporaryDirectory() as td, \
                    ThreadPoolExecutor(max_workers=1) as pool:
                ck = EngineCheckpointer(td, node="bench")
                # The snapshot callback gets freshly-materialized host
                # numpy (the pipeline's non-blocking copy), so the
                # serialize+publish rides a worker thread off the
                # dispatch path — XLA's compute doesn't hold the GIL,
                # so the write overlaps the next window's rounds.
                pending = []

                def save(r, s):
                    pending.append(pool.submit(ck.save, s, step=r))

                def drain():
                    for f in pending:
                        f.result()
                    pending.clear()

                run_once()  # warm: compile the window program
                run_once(EVERY, save, drain)  # warm serialize/write
                # Interleave the reps (plain, snap, plain, snap, ...)
                # and take mins: host-load drift during the tier hits
                # both legs instead of biasing the ratio.
                t_p, t_s = [], []
                for _ in range(4):
                    t_p.append(run_once())
                    t_s.append(run_once(EVERY, save, drain))
                t_plain, t_snap = min(t_p), min(t_s)
                published = ck.latest_step()
            overhead = t_snap / max(t_plain, 1e-9) - 1.0
            extra["elastic_snapshot"] = {
                "rounds": RS,
                "window": WS,
                "snapshot_every": EVERY,
                "snapshots_published_to_round": published,
                "plain_s": round(t_plain, 4),
                "snapshot_s": round(t_snap, 4),
                "overhead": round(overhead, 4),
                "within_5pct_budget": bool(overhead <= 0.05),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["elastic_error"] = str(e)[:200]


def _transformer_fed_tier(extra: dict) -> None:
    """Federated-transformer 2D-mesh tier (the ISSUE-15 workload: the
    engine federating a TransformerLM over a ``nodes x model`` mesh).
    One report, ``extra.transformer_fed``:

    - rounds/sec for the SAME federation at 1x1 (single device) and
      nodes=4 x model=2, plus MFU via the shared ``CostModel``
      (``analytic_train_flops`` now knows the transformer shape; MFU
      is None off-TPU like every other tier).
    - the per-device parameter-shard drop: the 4x2 run's per-device
      model-state bytes under the transformer SpecLayout vs the same
      mesh with the "replicated" layout — the layout's memory win,
      gated >= 1.5x at model=2 (sharded kernels/embeddings sit at
      ~2x; LayerNorm/bias leaves ride replicated). ``HbmTracker``
      peaks ride along where the backend reports memory stats (TPU).
    - acceptance booleans: 1x1-vs-4x2 steady-loss parity within 2%
      (accumulation tolerance — the reduction order changes), 4x2
      same-seed byte-determinism at the fixed mesh shape, and a CLEAN
      2D donation report (the sharded train+fold stages no copy).

    On a single-device CPU host the tier re-runs itself in a
    subprocess with 8 forced virtual devices (the multichip tier's
    discipline — forcing XLA_FLAGS process-wide would skew the other
    tiers' A/B budgets)."""
    import os

    import jax
    import numpy as np

    from tpfl.management.profiling import CostModel, HbmTracker
    from tpfl.settings import Settings

    try:
        cpu = jax.default_backend() == "cpu"
        if (
            cpu
            and len(jax.devices()) < 8
            and not os.environ.get("TPFL_TRANSFORMER_FED_SUB")
        ):
            import subprocess
            import sys as _sys

            # One process per chip: CPU parents only (the guard above),
            # child pinned to the CPU through its environment.
            env = dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                TPFL_TRANSFORMER_FED_SUB="1",
                XLA_FLAGS=(
                    os.environ.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=8"
                ).strip(),
            )
            proc = subprocess.run(
                [
                    _sys.executable,
                    os.path.abspath(__file__),
                    "--tiers",
                    "transformer_fed",
                ],
                capture_output=True,
                text=True,
                env=env,
                timeout=1800,
            )
            sub = json.loads(proc.stdout.splitlines()[-1])
            sub_extra = sub.get("extra", {})
            if "transformer_fed" in sub_extra:
                extra["transformer_fed"] = sub_extra["transformer_fed"]
                extra["transformer_fed"]["subprocess_devices"] = 8
            else:
                extra["transformer_fed_error"] = sub_extra.get(
                    "transformer_fed_error", "subprocess produced no tier"
                )
            return

        from tpfl.models import TransformerLM
        from tpfl.parallel import FederationEngine, create_mesh

        snap = Settings.snapshot()
        try:
            Settings.set_test_settings()
            # CPU CI shares one host's cores across the virtual
            # devices — a miniature LM keeps the tier in the smoke
            # budget; the TPU perf host runs a real long-context one.
            if cpu:
                nT, nbT, bsT, S_T = 8, 1, 4, 32
                lm_kw = dict(vocab=128, dim=64, heads=4, n_layers=2,
                             max_len=64)
                R_T, reps = 4, 2
            else:
                nT, nbT, bsT, S_T = 8, 1, 8, 2048
                lm_kw = dict(vocab=256, dim=512, heads=8, n_layers=4,
                             max_len=4096)
                R_T, reps = 8, 3
            module = TransformerLM(**lm_kw)
            rngT = np.random.default_rng(5)
            xsT = rngT.integers(0, lm_kw["vocab"], (nT, nbT, bsT, S_T)).astype(
                np.int32
            )
            ysT = rngT.integers(0, lm_kw["vocab"], (nT, nbT, bsT, S_T)).astype(
                np.int32
            )
            mesh_2d = create_mesh(
                {"nodes": 4, "model": 2}, devices=jax.devices()[:8]
            )

            def run(mesh, layout=None):
                """(engine, params out, mean last-round loss, rps)."""
                eng = FederationEngine(
                    module, nT, mesh=mesh, seed=0, learning_rate=0.05,
                    layout=layout,
                )
                p = eng.init_params((S_T,))
                dx, dy = eng.shard_data(xsT, ysT)
                p_out, losses = eng.run_rounds(
                    p, dx, dy, n_rounds=R_T, donate=False
                )  # warm: pays the compile
                jax.block_until_ready(losses)
                best = float("inf")
                for _ in range(reps):
                    t0 = time.monotonic()
                    p_out, losses = eng.run_rounds(
                        p, dx, dy, n_rounds=R_T, donate=False
                    )
                    jax.block_until_ready(losses)
                    best = min(best, time.monotonic() - t0)
                loss = float(
                    np.mean(np.asarray(eng.unpad(losses))[: eng.n_nodes])
                )
                return eng, p_out, loss, R_T / best

            _, _, loss_1, rps_1 = run(None)
            eng2, p_2d, loss_2, rps_2 = run(mesh_2d)

            def per_device_bytes(params):
                leaves = jax.tree_util.tree_leaves(params)
                return sum(
                    leaf.addressable_shards[0].data.nbytes for leaf in leaves
                )

            # The layout's memory win: same 4x2 mesh, transformer
            # layout vs node-replicated model state.
            _, p_repl, _, _ = run(mesh_2d, layout="replicated")
            sharded_b = per_device_bytes(p_2d)
            repl_b = per_device_bytes(p_repl)

            # Same-seed byte-determinism at the fixed 4x2 mesh shape.
            def digest():
                _, p, _, _ = run(mesh_2d)
                return b"".join(
                    np.asarray(leaf).tobytes()
                    for leaf in jax.tree_util.tree_leaves(p)
                )

            determinism = bool(digest() == digest())

            # Donation inspection on the 2D program (the run above
            # times donate=False fixed buffers; the donating variant
            # is the production path and must stay clean).
            engD = FederationEngine(
                module, nT, mesh=mesh_2d, seed=0, learning_rate=0.05
            )
            pD = engD.init_params((S_T,))
            dxD, dyD = engD.shard_data(xsT, ysT)
            report = engD.donation_report(pD, dxD, dyD, n_rounds=2)

            # MFU via the one shared CostModel path.
            samples_round = nT * nbT * bsT
            flops_round = CostModel.analytic_train_flops(
                module, (S_T,), samples_round
            )
            mfu_1 = mfu_2 = None
            if flops_round:
                mfu_1 = CostModel.mfu(flops_round * rps_1, n_chips=1)
                mfu_2 = CostModel.record_round(
                    "transformer_fed", flops_round, 1.0 / max(rps_2, 1e-9),
                    n_chips=8,
                )
            hbm = {
                dev: peak
                for dev, _used, peak in HbmTracker().sample()
            }
            rel = abs(loss_2 - loss_1) / max(abs(loss_1), 1e-9)
            extra["transformer_fed"] = {
                "nodes": nT,
                "seq_len": S_T,
                "rounds_per_window": R_T,
                "rps_1x1": round(rps_1, 3),
                "rps_4x2": round(rps_2, 3),
                "flops_per_round": flops_round,
                "mfu_1x1": mfu_1,
                "mfu_4x2": mfu_2,
                "param_bytes_per_device_4x2": int(sharded_b),
                "param_bytes_per_device_replicated": int(repl_b),
                "shard_bytes_ratio": round(repl_b / max(sharded_b, 1), 3),
                "shard_drop_ge_1_5x": bool(
                    repl_b >= 1.5 * max(sharded_b, 1)
                ),
                "loss_1x1": round(loss_1, 5),
                "loss_4x2": round(loss_2, 5),
                "loss_parity_rel": round(rel, 5),
                "parity_within_2pct": bool(rel <= 0.02),
                "determinism_byte_identical": determinism,
                "donation_clean": bool(report["clean"]),
                "donation_report": report,
                "hbm_peak_bytes": hbm,
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["transformer_fed_error"] = str(e)[:200]


def _byzantine_tier(extra: dict) -> None:
    """Active Byzantine defense tier (management/quarantine +
    aggregators/robust + attacks/plan). Four reports:

    - extra.byzantine_attack: seeded 10-node digits federation at 20%
      sign-flip + 20% additive-noise (AttackPlan schedule) — final
      honest-node accuracy for plain FedAvg (must measurably degrade
      vs the all-honest 10-node run), quarantined FedAvg and the
      quarantine-aware MultiKrum / TrimmedMean (must recover >= 95%
      of the ADVERSARY-FREE federation — the 6 honest nodes training
      alone, which is the information-theoretic ceiling for any
      defense: poisoned peers' data cannot be recovered, only their
      poison excluded), and Krum attacked-vs-its-own-fault-free
      robustness ratio (single-model selection converges slower than
      a mean, so its receipt is "the attack costs nothing", not "it
      matches FedAvg").
    - extra.byzantine_quarantine: the quarantine verdicts vs the
      plan's ground truth (exact set match).
    - extra.byzantine_determinism: two same-seed defended runs must
      produce byte-identical quarantine decision replays
      (quarantine.replay_decisions over the ledger's deduped view).
    - extra.byzantine_ab: defense-off vs defense-on rounds/sec at the
      fault-free 4-node scale every observability tier measures its
      tax at — the interleaved best-of-3 discipline, shared 5% budget.
    - extra.byzantine_async: the ASYNC variant — 20% replay adversaries
      (stale_flood + withhold_replay, attacks/plan.py) buffer-stuffing
      a 10-node serialized buffered-round federation: staleness-BLIND
      aggregation (ASYNC_STALENESS_EXP=0, defense off) degrades, the
      staleness-aware defended run (quarantine's stale_flood class +
      the FedBuff discount) recovers >= 0.95x the adversary-free async
      federation, and the quarantine set matches plan truth exactly.
    """
    from tpfl.management import ledger
    from tpfl.settings import Settings

    try:
        snap = Settings.snapshot()
        try:
            from tpfl.attacks import (
                AttackPlan,
                AttackSpec,
                adversary_map,
                metric_table,
                run_seeded_experiment,
            )
            from tpfl.learning.aggregators import (
                Krum,
                MultiKrum,
                TrimmedMean,
            )
            from tpfl.management import quarantine
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            seed = 4242
            rounds = 6
            adv_idx = {1, 4, 6, 8}  # 20% sign-flip + 20% noise of 10
            Settings.ELECTION = "hash"

            def attack_plan() -> AttackPlan:
                return AttackPlan(
                    {
                        1: AttackSpec("sign_flip"),
                        4: AttackSpec("sign_flip"),
                        6: AttackSpec("additive_noise", std=0.1),
                        8: AttackSpec("additive_noise", std=0.1),
                    },
                    seed=seed,
                )

            def honest_acc(exp: str, adv: "set | None" = None) -> float:
                """Mean test accuracy over honest nodes across the last
                two rounds (two rounds halve the per-node test-set
                quantization noise on the CPU-sized federation)."""
                tbl = metric_table(exp)
                vals = []
                for node in sorted(tbl):
                    if int(node.rsplit("n", 1)[1]) in (
                        adv_idx if adv is None else adv
                    ):
                        continue
                    series = tbl[node].get("test_metric", [])
                    vals.extend(v for _, v in series[-2:])
                return float(sum(vals) / max(len(vals), 1))

            def run_arm(
                attack: bool, defend: bool, agg_factory=None, n: int = 10
            ) -> "tuple[float, list, dict]":
                ledger.contrib.reset()
                Settings.QUARANTINE_ENABLED = defend
                Settings.LEDGER_ENABLED = defend
                Settings.TRAIN_SET_SIZE = n

                def data_fn(s):
                    # 3x the harness's default test split (the
                    # recovery RATIOS are gated, and small per-node
                    # test slices quantize accuracy), same 200 train
                    # samples per node at any federation size.
                    from tpfl.learning.dataset import rendered_digits

                    return rendered_digits(
                        n_train=200 * n, n_test=1200, seed=s
                    )

                exp = run_seeded_experiment(
                    seed, n, rounds, epochs=4,
                    attack_plan=attack_plan() if attack else None,
                    aggregator_factory=agg_factory,
                    data_fn=data_fn,
                    samples_per_node=200, batch_size=25,
                    learning_rate=0.1, timeout=300.0,
                )
                replay = quarantine.replay_decisions() if defend else []
                truth = adversary_map(exp) if attack else {}
                return honest_acc(exp), replay, truth

            base_acc, _, _ = run_arm(attack=False, defend=False)
            # The adversary-free federation: the 6 honest peers
            # training alone — what a perfect defense converges to.
            ideal_acc, _, _ = run_arm(attack=False, defend=False, n=6)
            plain_acc, _, _ = run_arm(attack=True, defend=False)
            quar_acc, replay1, truth = run_arm(attack=True, defend=True)
            _, replay2, _ = run_arm(attack=True, defend=True)
            krum_ff_acc, _, _ = run_arm(
                attack=False, defend=False,
                agg_factory=lambda: Krum(n_byzantine=3),
            )
            krum_at_acc, _, _ = run_arm(
                attack=True, defend=False,
                agg_factory=lambda: Krum(n_byzantine=3),
            )
            mk_acc, _, _ = run_arm(
                attack=True, defend=True,
                agg_factory=lambda: MultiKrum(n_byzantine=3, m=6),
            )
            tm_acc, _, _ = run_arm(
                attack=True, defend=True,
                agg_factory=lambda: TrimmedMean(trim=2),
            )

            def ratio(a: float, b: float) -> float:
                return round(a / max(b, 1e-9), 4)

            extra["byzantine_attack"] = {
                "seed": seed,
                "nodes": 10,
                "rounds": rounds,
                "adversaries": sorted(truth),
                "fault_free_acc": round(base_acc, 4),
                "adversary_free_acc": round(ideal_acc, 4),
                "plain_fedavg_acc": round(plain_acc, 4),
                "quarantined_fedavg_acc": round(quar_acc, 4),
                "krum_fault_free_acc": round(krum_ff_acc, 4),
                "krum_attacked_acc": round(krum_at_acc, 4),
                "multikrum_acc": round(mk_acc, 4),
                "trimmedmean_acc": round(tm_acc, 4),
                "plain_ratio": ratio(plain_acc, base_acc),
                "quarantined_ratio": ratio(quar_acc, ideal_acc),
                "krum_ratio": ratio(krum_at_acc, krum_ff_acc),
                "multikrum_ratio": ratio(mk_acc, ideal_acc),
                "trimmedmean_ratio": ratio(tm_acc, ideal_acc),
                # plain FedAvg must measurably degrade vs the
                # all-honest 10-node run; the defended arms must
                # recover >= 95% of the adversary-free federation
                # (measured ~0.98-1.01 — a defense cannot recover the
                # poisoned peers' DATA, only exclude their poison, so
                # the 10-node fault-free run is not the ceiling).
                # (Krum compares to its own fault-free run — see the
                # tier docstring.)
                "plain_degrades": bool(plain_acc <= 0.9 * base_acc),
                "quarantined_recovers": bool(quar_acc >= 0.95 * ideal_acc),
                "krum_robust": bool(krum_at_acc >= 0.9 * krum_ff_acc),
                "multikrum_recovers": bool(mk_acc >= 0.95 * ideal_acc),
                "trimmedmean_recovers": bool(tm_acc >= 0.95 * ideal_acc),
            }
            flagged = {
                a["peer"] for a in replay1 if a["action"] == "quarantine"
            }
            extra["byzantine_quarantine"] = {
                "flagged": sorted(flagged),
                "truth": sorted(truth),
                "exact_match": bool(flagged == set(truth)),
                "decisions": len(replay1),
            }
            extra["byzantine_determinism"] = {
                "byte_identical_decisions": bool(
                    json.dumps(replay1, sort_keys=True)
                    == json.dumps(replay2, sort_keys=True)
                ),
                "decisions_run1": len(replay1),
                "decisions_run2": len(replay2),
            }

            # Defense-off/on overhead A/B at the shared observability
            # scale (4 nodes, fault-free, 6 rounds): warm run first so
            # the quarantine stat fns compile outside the timed arms,
            # then interleave best-of-3. The defended arm enables the
            # DEFENSE alone (QUARANTINE_ENABLED activates the ledger's
            # scoring taps by itself) — the observational ledger's own
            # tax is budgeted separately by the ledger tier.
            def run_ab(defend: bool) -> float:
                ledger.contrib.reset()
                Settings.QUARANTINE_ENABLED = defend
                Settings.LEDGER_ENABLED = False
                Settings.TRAIN_SET_SIZE = 4
                t0 = time.monotonic()
                run_seeded_experiment(
                    2627, 4, 6,
                    samples_per_node=60, batch_size=20, timeout=240.0,
                )
                return time.monotonic() - t0

            # --- async variant: stale-flooding under buffered rounds ---
            # 20% replay adversaries (one stale_flood buffer-stuffing
            # version-0 junk from round 1, one withhold_replay turning
            # hostile at round 2 with a version-regressing tag) against
            # a 10-node serialized async federation, K = fleet,
            # ASYNC_STALENESS_MAX = 2 so the flood signature fires by
            # round 3. Staleness-BLIND aggregation (exp = 0, defense
            # off) folds the junk at full weight every round and
            # measurably degrades; the staleness-aware defended run
            # (quarantine + FedBuff discount) excludes it and recovers
            # >= 0.95x the adversary-free async federation (the
            # 8-honest-node ceiling — replayed peers' data cannot be
            # recovered, only their junk excluded). The quarantine
            # verdicts must match the plan's ground truth exactly.
            async_adv_idx = {1, 4}

            def async_attack_plan() -> AttackPlan:
                return AttackPlan(
                    {
                        1: AttackSpec("stale_flood"),
                        4: AttackSpec("withhold_replay", start=2),
                    },
                    seed=seed,
                )

            def run_async_arm(
                attack: bool, defend: bool, blind: bool = False, n: int = 10
            ) -> "tuple[float, list, dict]":
                ledger.contrib.reset()
                Settings.ASYNC_ROUNDS = True
                Settings.ASYNC_SERIALIZED = True
                Settings.ASYNC_ADAPTIVE = False
                Settings.ASYNC_BUFFER_K = n
                Settings.ASYNC_STALENESS_MAX = 2
                Settings.ASYNC_STALENESS_EXP = 0.0 if blind else 0.5
                Settings.QUARANTINE_ENABLED = defend
                Settings.LEDGER_ENABLED = defend
                Settings.TRAIN_SET_SIZE = n

                def data_fn(s):
                    from tpfl.learning.dataset import rendered_digits

                    return rendered_digits(
                        n_train=200 * n, n_test=1200, seed=s
                    )

                exp = run_seeded_experiment(
                    seed + 1, n, 8, epochs=4,
                    attack_plan=async_attack_plan() if attack else None,
                    data_fn=data_fn,
                    samples_per_node=200, batch_size=25,
                    learning_rate=0.1, timeout=600.0,
                )
                replay = quarantine.replay_decisions() if defend else []
                truth = adversary_map(exp) if attack else {}
                return honest_acc(exp, async_adv_idx), replay, truth

            a_ideal, _, _ = run_async_arm(attack=False, defend=False, n=8)
            a_blind, _, _ = run_async_arm(
                attack=True, defend=False, blind=True
            )
            a_def, a_replay, a_truth = run_async_arm(
                attack=True, defend=True
            )
            a_flagged = {
                a["peer"] for a in a_replay if a["action"] == "quarantine"
            }
            extra["byzantine_async"] = {
                "seed": seed + 1,
                "nodes": 10,
                "rounds": 8,
                "adversaries": sorted(a_truth),
                "adversary_free_acc": round(a_ideal, 4),
                "stale_blind_acc": round(a_blind, 4),
                "defended_acc": round(a_def, 4),
                "blind_ratio": ratio(a_blind, a_ideal),
                "defended_ratio": ratio(a_def, a_ideal),
                "flagged": sorted(a_flagged),
                "stale_flood_reasons": bool(
                    a_flagged
                    and all(
                        "stale_flood" in a["reasons"]
                        for a in a_replay
                        if a["action"] == "quarantine"
                    )
                ),
                # "Measurably degrades": the blind fold lands solidly
                # below the defended one on the SAME attacked run (the
                # most drift-stable comparison; measured 0.94 vs the
                # 0.98 gate) — the defended arm's own floor is gated
                # against the adversary-free ceiling below.
                "stale_degrades": bool(a_blind <= 0.98 * a_def),
                "defended_recovers": bool(a_def >= 0.95 * a_ideal),
                "quarantine_exact": bool(a_flagged == set(a_truth)),
            }
            # Restore the SYNC lifecycle for the A/B below.
            Settings.ASYNC_ROUNDS = False
            Settings.ASYNC_STALENESS_EXP = 0.5
            Settings.ASYNC_STALENESS_MAX = 16

            run_ab(True)  # warm
            off_times, on_times = [], []
            for _ in range(3):
                off_times.append(run_ab(False))
                on_times.append(run_ab(True))
            ab_rounds = 6
            off_rps = ab_rounds / max(min(off_times), 1e-9)
            on_rps = ab_rounds / max(min(on_times), 1e-9)
            overhead = 1.0 - on_rps / max(off_rps, 1e-9)
            extra["byzantine_ab"] = {
                "undefended": {
                    "elapsed_s": round(min(off_times), 2),
                    "rounds_per_s": round(off_rps, 3),
                },
                "defended": {
                    "elapsed_s": round(min(on_times), 2),
                    "rounds_per_s": round(on_rps, 3),
                },
                "overhead_frac": round(overhead, 4),
                "within_5pct_budget": bool(overhead < 0.05),
            }
        finally:
            Settings.restore(snap)
            ledger.contrib.reset()
    except Exception as e:
        extra["byzantine_error"] = str(e)[:200]


def _async_tier(extra: dict) -> None:
    """Asynchronous buffered rounds tier (stages.AsyncRoundStage +
    Aggregator async_k buffers + communication/faults.AsyncSchedule).
    Two reports:

    - extra.async_ab: a seeded 10-node digits federation under a
      TrainerSpeedPlan with a 10x-slower 20% tail — the exact fleet
      shape the synchronous barrier is worst at. The sync arm (vote
      lifecycle, full coverage) pays the slow trainers' fit time every
      round; the async arm (free-running FedBuff buffers, K=8) closes
      each round on the first 8 contributors and folds the stragglers
      later at staleness-discounted weight. Gates: async rounds/sec
      >= 1.5x sync, and async steady loss within 2% of sync.
    - extra.async_determinism: two same-seed SERIALIZED async runs
      (test profile discipline — the plan-seeded AsyncSchedule reorder
      buffer at every aggregator) must end with byte-identical global
      models, both across the two runs and across every node within a
      run (the fold sequence is position-deterministic, so all nodes
      converge on identical bytes). The adaptive controller
      (ASYNC_ADAPTIVE) is ON for these runs: its per-node K/deadline
      trajectories — derived from the schedule's virtual clock — must
      also come out identical.
    """
    from tpfl.settings import Settings

    try:
        snap = Settings.snapshot()
        try:
            from tpfl.attacks import metric_table, run_seeded_experiment
            from tpfl.attacks.harness import final_model_digests
            from tpfl.communication.faults import TrainerSpeedPlan
            from tpfl.management.logger import logger as _logger

            Settings.set_test_settings()
            Settings.LOG_LEVEL = "ERROR"
            _logger.set_level("ERROR")
            seed = 3131
            n = 10
            Settings.ELECTION = "hash"
            Settings.TRAIN_SET_SIZE = n
            # The async stage hints the pool with ASYNC_BUFFER_K so the
            # synchronized-fast fits co-batch; cap how long a partial
            # group may hold (the 5 s default would let the pool
            # rebuild the barrier the lifecycle removed). Same knob in
            # both arms — the sync arm's full groups never wait it out.
            Settings.SIM_BATCH_MAX_WAIT = 0.6

            def speed_plan() -> TrainerSpeedPlan:
                # 2 of 10 trainers 10x slower — seeded, address-pinned.
                return TrainerSpeedPlan.skewed(
                    [f"seed{seed}-n{i}" for i in range(n)],
                    slow_frac=0.2, base_delay=0.25, skew=10.0, seed=seed,
                )

            def mean_loss(exp: str) -> float:
                tbl = metric_table(exp)
                vals = [
                    tbl[node]["test_loss"][-1][1]
                    for node in sorted(tbl)
                    if tbl[node].get("test_loss")
                ]
                return float(sum(vals) / max(len(vals), 1))

            def run_arm(async_mode: bool, rounds: int) -> "tuple[float, float, str]":
                Settings.ASYNC_ROUNDS = async_mode
                # K well below the fleet: a buffer that needs a
                # contribution from every fast trainer is still a
                # barrier over the fast set (measured: K=8 of 8 fast
                # pinned speedup at ~1x; K=5 rides the first five
                # arrivals at better-than-sync steady loss).
                Settings.ASYNC_BUFFER_K = 5
                # Throughput arm runs FREE-RUNNING (the scale-profile
                # configuration): eager arrival-order folds, no
                # schedule — the determinism arm below exercises the
                # serialized discipline separately.
                Settings.ASYNC_SERIALIZED = False
                t0 = time.monotonic()
                exp = run_seeded_experiment(
                    seed, n, rounds, epochs=2,
                    speed_plan=speed_plan(),
                    samples_per_node=100, batch_size=25, timeout=600.0,
                )
                elapsed = time.monotonic() - t0
                return rounds / max(elapsed, 1e-9), mean_loss(exp), exp

            # Warm arm (compile) at the smallest useful size, then the
            # measured arms. The slow tail costs the SYNC arm ~1.2 s
            # per round; async closes on the fast 8.
            run_arm(True, 2)
            sync_rounds, async_rounds = 5, 10
            sync_rps, sync_loss, _ = run_arm(False, sync_rounds)
            async_rps, async_loss, _ = run_arm(True, async_rounds)
            speedup = async_rps / max(sync_rps, 1e-9)
            loss_ratio = async_loss / max(sync_loss, 1e-9)
            extra["async_ab"] = {
                "seed": seed,
                "nodes": n,
                "skew": "20% of trainers 10x slower (TrainerSpeedPlan)",
                "buffer_k": 5,
                "sync": {
                    "rounds": sync_rounds,
                    "rounds_per_s": round(sync_rps, 3),
                    "steady_loss": round(sync_loss, 4),
                },
                "async": {
                    "rounds": async_rounds,
                    "rounds_per_s": round(async_rps, 3),
                    "steady_loss": round(async_loss, 4),
                },
                "speedup": round(speedup, 3),
                "loss_ratio": round(loss_ratio, 4),
                "loss_within_2pct": bool(loss_ratio <= 1.02),
                "beats_sync_1_5x": bool(speedup >= 1.5),
            }

            # Same-seed byte-determinism under the serialized
            # discipline (test-profile configuration): the plan-seeded
            # AsyncSchedule makes every aggregator admit the identical
            # global contribution sequence, so the staleness-weighted
            # folds produce identical bytes at every node and in every
            # run.
            def run_det() -> "tuple[dict[str, str], dict]":
                Settings.ASYNC_ROUNDS = True
                Settings.ASYNC_BUFFER_K = 8
                Settings.ASYNC_SERIALIZED = True
                # The adaptive controller rides the determinism receipt:
                # serialized-mode observations come from the schedule's
                # VIRTUAL clock, so the per-node K/deadline trajectories
                # must also be byte-identical across same-seed runs.
                Settings.ASYNC_ADAPTIVE = True
                # Bit-exactness needs FIXED program shapes: the
                # batching pool's vmap bucket width follows whoever
                # co-submits (timing-dependent), and XLA compiles a
                # different reduction order per width. Inline learners
                # give every fit its own fixed-shape program — the
                # same rule the engine's byte-determinism contract
                # states (fixed device count / fixed shapes).
                Settings.DISABLE_SIMULATION = True
                exp = run_seeded_experiment(
                    seed, n, 4, epochs=2,
                    speed_plan=speed_plan(),
                    samples_per_node=100, batch_size=25, timeout=600.0,
                )
                from tpfl.attacks.harness import controller_trajectories

                return final_model_digests(exp), controller_trajectories(exp)

            (d1, t1), (d2, t2) = run_det(), run_det()
            extra["async_determinism"] = {
                "byte_identical": bool(
                    d1 == d2 and len(set(d1.values())) == 1
                ),
                "runs_match": bool(d1 == d2),
                "nodes_converged_identical": len(set(d1.values())) == 1,
                "digest": sorted(set(d1.values()))[:1],
                "controller_trajectories_identical": bool(
                    t1 == t2 and all(t1.values())
                ),
            }
        finally:
            Settings.restore(snap)
    except Exception as e:
        extra["async_error"] = str(e)[:200]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--profile",
        metavar="DIR",
        default=None,
        help="write a jax.profiler trace of the primary timed region "
        "to DIR (view with TensorBoard/xprof)",
    )
    ap.add_argument(
        "--tiers",
        metavar="CSV",
        default="all",
        help=f"comma-separated tiers to run (default all): {', '.join(TIERS)}",
    )
    ap.add_argument(
        "--check",
        metavar="BASELINE",
        default=None,
        help="perf regression gate: compare this run's metrics against "
        "the committed baseline JSON; exit nonzero on regression",
    )
    ap.add_argument(
        "--results",
        metavar="FILE",
        default=None,
        help="with --check: gate an EXISTING bench output file instead "
        "of running any tiers",
    )
    args = ap.parse_args()

    import sys

    if args.results:
        # Pure gate mode: no tiers, no jax import — the CI-cheap path
        # (and the one tests drive with fixture documents).
        if not args.check:
            raise SystemExit("--results requires --check BASELINE")
        with open(args.results, encoding="utf-8") as f:
            doc = json.load(f)
        rc = _check_verdict(doc, args.check)
        print(json.dumps({"check": doc["extra"]["check"]}))
        sys.exit(rc)

    tiers = _parse_tiers(args.tiers)

    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpfl.management import profiling
    from tpfl.models import CNN, MLP, ResNet18
    from tpfl.parallel import VmapFederation, device_report, require_chip

    # Persistent compile cache: the big vmapped round programs dominate
    # bench wall-clock; repeat runs hit disk. ONE rule, shared with
    # chip_smoke.py and the examples (profiling.compile_cache_dir):
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache.
    profiling.ensure_compile_cache()

    # The five tiers whose numbers are device rates: they FAIL without a
    # TPU of a kind the peaks table knows — a CPU fallback printing the
    # same rate keys is how a lost chip went unnoticed. (multichip also
    # times a device on the chip, but keeps its CPU sizing: it is the CI
    # receipt for the engine's determinism booleans.)
    chip_tiers = {"primary", "resnet", "attention", "transformer", "sim1000"}
    device = require_chip() if tiers & chip_tiers else device_report()
    n_chips = device["count"]
    extra: dict = {
        "chips": n_chips,
        "real_image_data": True,
        "tiers": sorted(tiers),
    }
    peak = _peak_flops(jax.devices()[0])

    # Shared empty-call dispatch RTT baseline, measured ONCE for every
    # device tier (profiling.measure_dispatch_rtt — the generalized
    # bench methodology: one dispatch+sync round trip of a trivially
    # small program, subtracted from every window timing).
    rtt = None
    if tiers & (chip_tiers | {"multichip"}):
        rtt = profiling.measure_dispatch_rtt()
        extra["dispatch_rtt_ms"] = round(rtt * 1e3, 1)

    def _timed_loop(step, carry, data, n_iters):
        """Device-side seconds/iteration — profiling.timed_loop with
        the shared RTT baseline. One methodology for EVERY tier
        (docs/perf_cnn.md:11-26 is the anchor); the implementation now
        lives in tpfl.management.profiling so the framework and the
        bench can never drift."""
        return profiling.timed_loop(step, carry, data, n_iters, rtt=rtt)

    # ---- shared prerequisites ----
    # Analytic CNN model flops through the unified CostModel (2·M·K·N
    # per conv/dense layer, x3 fwd+bwd) — derived from the zoo CNN's
    # actual config so a model change can never silently desynchronize
    # the MFU accounting; immune to cost_analysis scan-once counting
    # and custom-VJP lowering.
    n_nodes = 100 if n_chips == 1 else (100 // n_chips) * n_chips
    n_batches, batch_size, epochs = 4, 128, 1
    samples_per_round = n_nodes * n_batches * batch_size * epochs
    cnn_cfg = CNN(out_channels=10)
    per_sample_fwd = 2 * profiling.cost_model.analytic_fwd_mults(
        cnn_cfg, (32, 32, 3)
    )
    round_flops = 3 * per_sample_fwd * samples_per_round

    params = None
    x_all = y_all = None
    rounds_per_sec = 0.0
    samples_per_sec_chip = 0.0

    if tiers & {"primary", "resnet", "wire", "serde"}:
        mesh = None
        if n_chips > 1 and "primary" in tiers:
            from tpfl.parallel import create_mesh

            mesh = create_mesh({"nodes": n_chips})

        def cnn_fed(n, m=None):
            return VmapFederation(
                CNN(out_channels=10), n_nodes=n, mesh=m, learning_rate=0.1, seed=0
            )

        fed = cnn_fed(n_nodes, mesh)
        params = fed.init_params((32, 32, 3))
    if tiers & {"primary", "resnet"}:
        from tpfl.learning.dataset.rendered import rendered_color_digits

        per_node = n_batches * batch_size
        ds = rendered_color_digits(n_train=n_nodes * per_node, n_test=10, seed=0)
        x_all = np.asarray(ds.get_split(True)["image"], np.float32)
        y_all = np.asarray(ds.get_split(True)["label"], np.int32)

    # ---- primary: 100-node CNN on rendered color digits (config 2) ----
    # Per-node batch 128 (not the reference-style 32): at 32 the round is
    # launch-overhead-bound and the MXU idles; 128 is compute-honest and
    # is what a TPU user would run.
    if "primary" in tiers:
        xs = x_all.reshape(n_nodes, n_batches, batch_size, 32, 32, 3)
        ys = y_all.reshape(n_nodes, n_batches, batch_size)
        # Feed bf16: the CNN computes in bf16 anyway — shipping f32
        # inputs just doubles the HBM traffic of every epoch's reads.
        xs, ys = fed.shard_data(jnp.asarray(xs, jnp.bfloat16), ys)

        # Device-side timing: K rounds per dispatch inside one
        # fori_loop, so the dispatch+sync round trip (subtracted as
        # `rtt`) is paid once per window and host-loop timing cannot
        # misattribute it. Since PR 9 the multi-round window is
        # FRAMEWORK API (`FederationEngine.run_rounds` — the same
        # program `FederationLearner` dispatches per
        # SHARD_ROUNDS_PER_DISPATCH window); the tier drives that seam
        # instead of a bench-local fori_loop, so the measured number IS
        # the framework path, engine overhead included (docs/perf_cnn.md
        # round 7). Since round 13 the tier times the DONATING program
        # — the real production variant, state buffers aliased in place
        # — via best_of_wall_donated: each iteration threads the
        # window's own output params back in as the next donated input
        # (the FederationLearner shape), instead of building a
        # donate=False program just to be timeable.
        w_ones = jnp.ones((n_nodes,), jnp.float32)
        R_INNER = 20

        def run_window(p, xs, ys, w):
            return fed.run_rounds(
                p, xs, ys, weights=w, epochs=epochs, n_rounds=R_INNER,
                donate=True,
            )

        with profiling.maybe_trace(args.profile):
            total, (params, losses) = profiling.best_of_wall_donated(
                run_window, (params, xs, ys, w_ones),
                rebind=lambda out, a: (out[0], *a[1:]),
            )
        per_round = max(total - rtt, 1e-9) / R_INNER
        rounds_per_sec = 1.0 / per_round
        samples_per_sec_chip = rounds_per_sec * samples_per_round / n_chips
        extra["steady_loss"] = round(float(np.asarray(losses).mean()), 4)
        if args.profile:
            extra["profile_dir"] = args.profile

        if peak:
            extra["round_tflops"] = round(round_flops / 1e12, 3)
            extra["mfu"] = round(
                rounds_per_sec * round_flops / (peak * n_chips), 4
            )
            extra["mfu_method"] = (
                "analytic 2MKN model flops x3 (CostModel); device "
                "fori-loop timing, RTT-subtracted"
            )
            # Live MFU through the registry gauge — the SAME CostModel
            # path the profiling tier cross-checks against the analytic
            # column above.
            live = profiling.cost_model.record_round(
                "cnn_primary", round_flops, per_round, n_chips=n_chips
            )
            if live is not None:
                extra["profiling_live_mfu"] = round(live, 4)

        # ---- MFU floor: shared-weight train step, measured IN-BENCH ----
        # The fundamental ceiling for this model/batch — ONE set of weights,
        # no federation at all (docs/perf_cnn.md's floor, r4: 12.0% on
        # v5e). Measured here every run so mfu_vs_floor is a computed
        # ratio, never a stale quoted constant.
        try:
            import optax

            floor_model = CNN(out_channels=10)
            fvars = floor_model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
            )
            fopt = optax.sgd(0.1, momentum=0.9)
            fp, fo = fvars["params"], fopt.init(fvars["params"])
            fx = jnp.asarray(x_all[:batch_size], jnp.bfloat16)
            fy = jnp.asarray(y_all[:batch_size])

            def floor_step(c, x, y):
                p, o, _ = c

                def loss_of(pp):
                    logits = floor_model.apply({"params": pp}, x, train=False)
                    return optax.softmax_cross_entropy_with_integer_labels(
                        logits, y
                    ).mean()

                loss, grads = jax.value_and_grad(loss_of)(p)
                upd, o = fopt.update(grads, o, p)
                return optax.apply_updates(p, upd), o, loss

            per_step, _ = _timed_loop(
                # 8000 iters ≈ 0.9 s of device work at the pre-PR-1
                # ~110 us/step: the subtracted RTT (and its run-to-run
                # drift) must stay a small share of the measurement.
                floor_step, (fp, fo, jnp.float32(0)), (fx, fy), 8000
            )
            if peak:
                mfu_floor = (3 * per_sample_fwd * batch_size) / (per_step * peak)
                extra["mfu_floor"] = round(mfu_floor, 4)
                extra["mfu_vs_floor"] = round(extra["mfu"] / mfu_floor, 3)
        except Exception as e:
            extra["mfu_floor_error"] = str(e)[:200]

    if "resnet" in tiers:
        # ---- config 3 tier: ResNet-18 (BatchNorm aux path), CIFAR-100,
        # with ALL THREE BASELINE aggregators: FedAvg, SCAFFOLD, FedProx
        # (BASELINE.md:35 names "Scaffold / FedProx aggregators on
        # CIFAR-100 ResNet-18" — benched here as written, through the
        # vectorized control-variate / proximal round programs,
        # tpfl/parallel/federation.py). bs 128: the first compute-dense
        # tier — at bs=32 it measured scheduling overhead (19% MFU), at
        # 128 the MXU is genuinely busy.
        n3, nb3, bs3 = 16, 2, 128

        def rn_fed(n, **kw):
            return VmapFederation(
                ResNet18(out_channels=100), n_nodes=n, learning_rate=0.1,
                seed=0, **kw,
            )

        xs3 = jnp.asarray(
            x_all[: n3 * nb3 * bs3].reshape(n3, nb3, bs3, 32, 32, 3),
            jnp.bfloat16,
        )
        ys3 = jnp.asarray(y_all[: n3 * nb3 * bs3].reshape(n3, nb3, bs3))
        w3 = jnp.ones((n3,), jnp.float32)
        R3 = 6
        rn_flops = _round_flops_estimate(
            rn_fed, (32, 32, 3), (bs3, 32, 32, 3), n3, nb3, 1, aux=True
        )
        extra["resnet18_cfg3_nodes"] = n3

        def bench_resnet(key: str, algorithm: str) -> None:
            try:
                fed3 = rn_fed(n3, algorithm=algorithm)
                p3, a3 = fed3.init_state((32, 32, 3))
                if algorithm == "scaffold":
                    sc = fed3.init_scaffold_state(p3)
                    rfn = fed3._build_round_scaffold()

                    def step(c, xs, ys):
                        p, cl, cg, a, _ = c
                        p, cl, cg, a, losses = rfn(p, cl, cg, a, xs, ys, w3, 1)
                        return p, cl, cg, a, losses

                    carry = (p3, sc[0], sc[1], a3, jnp.zeros((n3,), jnp.float32))
                else:
                    rfn = fed3._build_round_aux()

                    def step(c, xs, ys):
                        p, a, _ = c
                        p, a, losses = rfn(p, a, xs, ys, w3, 1)
                        return p, a, losses

                    carry = (p3, a3, jnp.zeros((n3,), jnp.float32))
                per_round, _ = _timed_loop(step, carry, (xs3, ys3), R3)
                rps3 = 1.0 / per_round
                # Runs mesh-less on ONE device — that device's throughput
                # IS the per-chip number regardless of host chip count.
                extra[f"{key}_samples_per_sec_chip"] = round(
                    rps3 * n3 * nb3 * bs3, 1
                )
                if rn_flops and peak:
                    # Model flops only (the FedAvg estimate): SCAFFOLD /
                    # FedProx extras (variate updates, proximal pull) are
                    # O(params)/O(1-pass) — their cost shows up as a LOWER
                    # model-flops MFU on the same denominator, which is
                    # exactly the overhead being measured.
                    extra[f"{key}_mfu"] = round(rps3 * rn_flops / peak, 4)
            except Exception as e:  # keep the primary metric alive
                extra[f"{key}_error"] = str(e)[:200]

        if rn_flops and peak:
            extra["resnet18_cfg3_round_tflops"] = round(rn_flops / 1e12, 3)
        bench_resnet("resnet18_cfg3", "fedavg")
        bench_resnet("resnet18_scaffold", "scaffold")
        bench_resnet("resnet18_fedprox", "fedprox")

    if "attention" in tiers:
        # ---- long-context tier: flash kernel vs XLA blockwise, fwd+bwd ----
        # The kernel must EARN its keep in training (custom VJP), so the
        # comparison times gradient steps, not forwards. Device-side
        # timing like every tier: K grad steps per dispatch, the grads fed
        # back into the next iteration's inputs at negligible magnitude so
        # XLA cannot elide the loop body.
        try:
            from tpfl.parallel.flash_kernel import flash_attention
            from tpfl.parallel.ring_attention import blockwise_attention

            def time_attn(fn, S, n_iters):
                B, H, D = 1, 8, 128
                rng = np.random.default_rng(0)
                q, k, v = (
                    jnp.asarray(
                        rng.normal(size=(B, S, H, D)), jnp.bfloat16
                    )
                    for _ in range(3)
                )

                def loss(q, k, v):
                    return jnp.sum(
                        fn(q, k, v, causal=True).astype(jnp.float32) ** 2
                    )

                def step(c):
                    q, k, v = c
                    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                    return (
                        q - 1e-6 * dq.astype(q.dtype),
                        k - 1e-6 * dk.astype(k.dtype),
                        v - 1e-6 * dv.astype(v.dtype),
                    )

                per_iter, _ = _timed_loop(step, (q, k, v), (), n_iters)
                return B * S / per_iter

            # Iteration counts sized for ≥ ~0.8 s of device work per tier
            # (pre-PR-1 figure: 8k fwd+bwd in ~4.4 ms), so the subtracted
            # RTT's run-to-run drift stays a small share of the total.
            for S, iters in ((8192, 192), (32768, 16)):
                for name, fn in (
                    ("flash", flash_attention),
                    (
                        "blockwise",
                        lambda q, k, v, causal: blockwise_attention(
                            q, k, v, causal=causal
                        ),
                    ),
                ):
                    key = f"{name}_fwdbwd_{S//1024}k_toks_per_sec"
                    try:  # each measurement independent: the XLA blockwise
                        # grad at 32k can exceed compiler limits; that must
                        # not cost the kernel its numbers.
                        extra[key] = round(time_attn(fn, S, iters), 1)
                    except Exception as e:
                        extra[key + "_error"] = str(e)[:160]

            # Sequence-parallel path A/B: the SAME ring_attention entry,
            # flash inner vs the old einsum inner, on a 1-device sp mesh
            # (ring machinery identical, only the inner differs — the r4
            # verdict's "flash never rides the sp path" gap). The XLA
            # inner materializes O(lq²) scores, so it only fits at 8k;
            # the flash inner also runs 32k.
            from tpfl.parallel import create_mesh as _cm
            from tpfl.parallel.ring_attention import make_ring_attention

            sp_mesh = _cm({"sp": 1})
            for S, iters, impls in (
                (8192, 192, ("flash", "xla")),
                (32768, 16, ("flash",)),
            ):
                for impl in impls:
                    key = f"ring_sp_{impl}_fwdbwd_{S//1024}k_toks_per_sec"
                    try:
                        ring_fn = make_ring_attention(
                            sp_mesh, causal=True, impl=impl
                        )

                        def ring_adapter(q, k, v, causal=True, _f=ring_fn):
                            return _f(q, k, v)

                        extra[key] = round(time_attn(ring_adapter, S, iters), 1)
                    except Exception as e:
                        extra[key + "_error"] = str(e)[:160]
        except Exception as e:
            extra["flash_attn_error"] = str(e)[:200]

    if "transformer" in tiers:
        # ---- transformer_sp tier: TransformerLM training at 32k tokens ----
        try:
            from tpfl.models import TransformerLM
            from tpfl.parallel.flash_kernel import flash_attention as _fa

            S_lm = 32768
            lm = TransformerLM(
                vocab=256, dim=512, heads=8, n_layers=4, max_len=S_lm,
                attention_fn=_fa,
            )
            rng = np.random.default_rng(0)
            toks = jnp.asarray(
                rng.integers(0, 256, (1, S_lm)), jnp.int32
            )
            variables = lm.init(jax.random.PRNGKey(0), toks[:, :128], train=False)
            import optax

            tx = optax.sgd(1e-2, momentum=0.9)
            lm_params = variables["params"]
            lm_opt = tx.init(lm_params)

            def lm_step(c, t):
                p, o, _ = c

                def loss_of(pp):
                    logits = lm.apply({"params": pp}, t, train=True)
                    return optax.softmax_cross_entropy_with_integer_labels(
                        logits[:, :-1], t[:, 1:]
                    ).mean()

                loss, grads = jax.value_and_grad(loss_of)(p)
                upd, o = tx.update(grads, o, p)
                return optax.apply_updates(p, upd), o, loss

            per_step, _ = _timed_loop(
                lm_step, (lm_params, lm_opt, jnp.float32(0)), (toks,), 5
            )
            extra["transformer_32k_train_toks_per_sec"] = round(
                S_lm / per_step, 1
            )
        except Exception as e:
            extra["transformer_lm_error"] = str(e)[:200]

    if "sim1000" in tiers:
        # ---- config 4 tier: 1000 nodes, 10% partial participation ----
        try:
            n4, nb4, bs4 = 1000, 1, 32
            fed4 = VmapFederation(
                MLP(hidden_sizes=(64,)), n_nodes=n4, learning_rate=0.1, seed=0
            )
            p4 = fed4.init_params((28, 28))
            rng = np.random.default_rng(0)
            xs4 = rng.random((n4, nb4, bs4, 28, 28), np.float32)
            ys4 = rng.integers(0, 10, (n4, nb4, bs4)).astype(np.int32)
            w4 = jnp.asarray(
                (rng.random(n4) < 0.1).astype(np.float32)
            )  # ~100 elected/round
            if fed4._round_fn is None:
                fed4._round_fn = fed4._build_round()
            round4 = fed4._round_fn

            def step4(c, xs, ys):
                p, _ = c
                p, losses = round4(p, xs, ys, w4, 1)
                return p, losses

            per_round4, _ = _timed_loop(
                step4,
                (p4, jnp.zeros((n4,), jnp.float32)),
                (jnp.asarray(xs4), jnp.asarray(ys4)),
                400,
            )
            extra["sim1000_partial_rounds_per_sec"] = round(1.0 / per_round4, 2)
        except Exception as e:
            extra["sim1000_error"] = str(e)[:200]

    if "wire" in tiers:
        # ---- wire codec tier: dense-vs-codec payload bytes, encode/decode
        # throughput, and a SEEDED digits convergence A/B. The protocol-
        # scale runs are gossip-bound (docs/deployment.md), so the codec's
        # byte reduction is the round-time lever; the A/B proves the lossy
        # codec ("quant8+zlib" + residual round-result payloads, the scale
        # profile's wire config) converges within noise of the dense wire
        # on the same seeded run. Same-seed two-run comparison, harness
        # style (attacks/harness.py): identical data, init, and batch
        # order — the ONLY difference is the wire round-trip.
        try:
            import hashlib

            from tpfl.learning import compression
            from tpfl.learning import serialization as ser

            AB_CODEC = "quant8+zlib"

            # Encode/decode throughput on the flagship CNN's params (what
            # a real gossip push moves), best of 3, MB/s of DENSE payload
            # size so dense and codec rates are comparable work rates.
            cnn_host = jax.tree_util.tree_map(np.asarray, params)
            dense_blob = ser.encode_model_payload(cnn_host, ["bench"], 1, {})
            codec_blob = compression.encode_model_payload(
                cnn_host, ["bench"], 1, {}, AB_CODEC
            )
            mb = len(dense_blob) / 1e6

            def _rate(fn, n=3):
                best = float("inf")
                fn()  # warm (jit caches, zlib tables)
                for _ in range(n):
                    t0 = time.perf_counter()
                    fn()
                    best = min(best, time.perf_counter() - t0)
                return mb / best

            extra["wire_dense_payload_bytes"] = len(dense_blob)
            extra["wire_codec_payload_bytes"] = len(codec_blob)
            extra["wire_codec"] = AB_CODEC
            extra["wire_payload_ratio"] = round(
                len(dense_blob) / len(codec_blob), 2
            )
            extra["wire_encode_dense_MBps"] = round(
                _rate(lambda: ser.encode_model_payload(cnn_host, ["b"], 1, {})), 1
            )
            extra["wire_encode_codec_MBps"] = round(
                _rate(
                    lambda: compression.encode_model_payload(
                        cnn_host, ["b"], 1, {}, AB_CODEC
                    )
                ),
                1,
            )
            extra["wire_decode_dense_MBps"] = round(
                _rate(lambda: ser.decode_model_payload(dense_blob)), 1
            )
            extra["wire_decode_codec_MBps"] = round(
                _rate(lambda: compression.decode_model_payload(codec_blob)), 1
            )

            # Seeded digits A/B: 4-node FedAvg on rendered digits, every
            # payload (4 uploads + the result broadcast per round) pushed
            # through the wire; the codec run additionally ships the
            # broadcast as a residual against the previous round's
            # round-tripped aggregate (delta gossip).
            import optax

            from tpfl.learning.dataset.rendered import rendered_digits
            from tpfl.models import MLP as _MLP

            AB_NODES, AB_BATCHES, AB_BS, AB_ROUNDS = 4, 2, 64, 10
            dsd = rendered_digits(
                n_train=AB_NODES * AB_BATCHES * AB_BS, n_test=10, seed=0
            )
            dx = np.asarray(dsd.get_split(True)["image"], np.float32).reshape(
                AB_NODES, AB_BATCHES, AB_BS, 28, 28
            )
            dy = np.asarray(dsd.get_split(True)["label"], np.int32).reshape(
                AB_NODES, AB_BATCHES, AB_BS
            )
            ab_mlp = _MLP(hidden_sizes=(32,), compute_dtype=jnp.float32)
            ab_p0 = ab_mlp.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)), train=False
            )["params"]
            # lr sized so the seeded run is mid-DESCENT at the comparison
            # point (a flat-at-init loss would match trivially): 2.30 ->
            # ~1.83 over the 10 rounds on CPU and TPU alike.
            ab_tx = optax.sgd(0.5)

            @jax.jit
            def ab_fit(p, x, y):
                o = ab_tx.init(p)
                loss = jnp.float32(0)
                for b in range(AB_BATCHES):
                    def loss_of(pp):
                        logits = ab_mlp.apply({"params": pp}, x[b], train=True)
                        return optax.softmax_cross_entropy_with_integer_labels(
                            logits, y[b]
                        ).mean()

                    loss, g = jax.value_and_grad(loss_of)(p)
                    upd, o = ab_tx.update(g, o, p)
                    p = optax.apply_updates(p, upd)
                return p, loss

            def ab_run(codec: "str | None") -> tuple[int, float]:
                """One seeded federation; codec=None -> dense v1 wire.
                Returns (total payload bytes, steady loss)."""
                g = jax.tree_util.tree_map(np.asarray, ab_p0)
                total = 0
                base = None  # (round, fp, params) of last broadcast
                steady = 0.0
                for r in range(AB_ROUNDS):
                    locals_, losses = [], []
                    for i in range(AB_NODES):
                        pi, li = ab_fit(g, dx[i], dy[i])
                        pi = jax.tree_util.tree_map(np.asarray, pi)
                        if codec is None:
                            blob = ser.encode_model_payload(pi, [f"n{i}"], 1, {})
                            back = ser.decode_model_payload(blob)[0]
                        else:
                            blob = compression.encode_model_payload(
                                pi, [f"n{i}"], 1, {}, codec
                            )
                            back = compression.decode_model_payload(blob)[0]
                        total += len(blob)
                        locals_.append(back)
                        losses.append(float(li))
                    agg = jax.tree_util.tree_map(
                        lambda *xs: np.mean(np.stack(xs), axis=0), *locals_
                    )
                    if codec is None:
                        blob = ser.encode_model_payload(agg, ["agg"], 1, {})
                        g = ser.decode_model_payload(blob)[0]
                    else:
                        cache = compression.BaseCache()
                        delta_base = None
                        if base is not None:
                            delta_base = base
                            cache.put(base[0], base[2])
                        blob = compression.encode_model_payload(
                            agg, ["agg"], 1, {}, codec, delta_base=delta_base
                        )
                        g = compression.decode_model_payload(blob, bases=cache)[0]
                        base = (r, compression.pytree_fingerprint(g), g)
                    # one result broadcast per non-trainer peer in the real
                    # protocol; count the fan-out the dense run also pays
                    total += len(blob) * (AB_NODES - 1)
                    steady = float(np.mean(losses))
                return total, steady

            dense_bytes, dense_loss = ab_run(None)
            codec_bytes, codec_loss = ab_run(AB_CODEC)
            rel = abs(codec_loss - dense_loss) / max(abs(dense_loss), 1e-9)
            extra["wire_ab"] = {
                "codec": AB_CODEC + "+delta",
                "dense_bytes": dense_bytes,
                "codec_bytes": codec_bytes,
                "bytes_ratio": round(dense_bytes / codec_bytes, 2),
                "dense_steady_loss": round(dense_loss, 4),
                "codec_steady_loss": round(codec_loss, 4),
                "steady_loss_rel_diff": round(rel, 4),
                "within_2pct": bool(rel <= 0.02),
                "ge_4x_bytes": bool(dense_bytes / codec_bytes >= 4.0),
            }
        except Exception as e:
            extra["wire_codec_error"] = str(e)[:200]

    # Serde tier: v1-vs-v3 encode/decode GB/s, aggregation peak RSS vs
    # contributor count, in-process zero-copy A/B
    # (extra.serde / extra.serde_agg_peak / extra.serde_inproc_ab).
    if "serde" in tiers:
        _serde_tier(extra, jax.tree_util.tree_map(np.asarray, params))

    # Chaos tier: deterministic fault accounting + live faulted A/B
    # (extra.chaos_determinism / extra.chaos_ab).
    if "chaos" in tiers:
        _chaos_tier(extra)

    # Analysis tier: tpflcheck suite wall-time + lock-traced federation
    # A/B (extra.analysis_static / extra.analysis_lock_trace).
    if "analysis" in tiers:
        _analysis_tier(extra)

    # Telemetry tier: trace-id determinism, tracing-enabled overhead
    # A/B + hop-path reconstruction, registry fold sanity
    # (extra.telemetry_determinism / telemetry_ab / telemetry_registry).
    if "telemetry" in tiers:
        _telemetry_tier(extra)

    # Profiling tier: observatory shape-churn probe, profiled-run
    # overhead A/B + round attribution coverage, live-vs-analytic MFU
    # (extra.profiling_compile / profiling_ab / profiling_mfu).
    if "profiling" in tiers:
        _profiling_tier(extra)

    # Ledger tier: seeded adversarial federation — anomaly-detection
    # precision/recall vs the harness ground truth, same-seed flag
    # determinism, ledger off/on overhead A/B
    # (extra.ledger_detection / ledger_determinism / ledger_ab).
    if "ledger" in tiers:
        _ledger_tier(extra)

    if "byzantine" in tiers:
        _byzantine_tier(extra)

    # Engine-plane telemetry tier: program split + byte determinism,
    # in-program sign-flip adversary through ledger/quarantine, carry
    # off/on overhead A/B (extra.engine_obs_program /
    # engine_obs_detection / engine_obs_ab).
    if "engine_obs" in tiers:
        _engine_obs_tier(extra)

    # Device-side wire codec + donation tier: codec-off HLO identity,
    # donation-clean compiled HLO + donate/no-donate byte identity,
    # dense-vs-quant8 device-side bytes/round, quantized loss parity
    # (extra.engine_wire_program / engine_wire_bytes /
    # engine_wire_parity).
    if "engine_wire" in tiers:
        _engine_wire_tier(extra)

    # Free-running engine tier: fedbuff-vs-sync virtual throughput
    # under a 10x-skewed tail, pipelined-vs-sequential device-idle gap
    # (with byte identity), same-seed pipelined fedbuff determinism at
    # 1 and 8 devices (extra.engine_async_throughput /
    # engine_async_pipeline / engine_async_determinism). Self-provisions
    # the 8-device leg in a subprocess on single-device CPU hosts.
    if "engine_async" in tiers:
        _engine_async_tier(extra)

    # Elastic engine tier: 20-event membership churn storm with the
    # CompileObservatory's recompiles == promotions receipt, masked-vs-
    # exact byte identity at matched padded sizes, the kill-and-resume
    # equivalence digest, and the cadence-snapshot ≤5% overhead budget
    # (extra.elastic_storm / elastic_masked / elastic_resume /
    # elastic_snapshot). Self-provisions the 8-device masked leg in a
    # subprocess on single-device CPU hosts.
    if "elastic" in tiers:
        _elastic_tier(extra)

    # Async tier: FedBuff-style buffered rounds vs the synchronous
    # barrier under a 10x-skewed trainer fleet, plus the serialized
    # same-seed byte-determinism receipt
    # (extra.async_ab / extra.async_determinism).
    if "async" in tiers:
        _async_tier(extra)

    # Federated-transformer 2D-mesh tier: TransformerLM rounds/sec +
    # MFU at 1x1 vs nodes=4 x model=2, the per-device parameter-shard
    # drop under the SpecLayout, parity/determinism/donation booleans
    # (extra.transformer_fed). Self-provisions 8 virtual devices in a
    # subprocess on single-device CPU hosts, like multichip below.
    if "transformer_fed" in tiers:
        _transformer_fed_tier(extra)

    # multichip runs LAST: its 8-virtual-device subprocess and big
    # stacked allocations must not perturb the budget-sensitive
    # off/on A/Bs (profiling/ledger/byzantine) in this process.
    if "multichip" in tiers:
        # ---- multichip tier: the pod-scale federation engine ----
        # sim1000 promoted to the mesh (tpfl/parallel/engine.py): the
        # ENTIRE federation round — per-node train, gossip-as-psum
        # exchange, streaming fold — is one sharded XLA program over a
        # `nodes` mesh, and R_WIN rounds run per dispatch inside a
        # device-side fori_loop (the host dispatch RTT paid once
        # per window). Reports rounds/sec per device count, scaling
        # efficiency, same-seed byte-determinism at fixed device count,
        # window-vs-sequential equivalence, the engine-vs-legacy-path
        # ratio, and the sim100k cross-device smoke (population state
        # O(active), not O(population)).
        try:
            import resource

            from tpfl.parallel import (
                FederationEngine,
                create_mesh,
                sample_participants,
            )

            cpu = jax.default_backend() == "cpu"
            if (
                cpu
                and n_chips == 1
                and not os.environ.get("TPFL_MULTICHIP_SUB")
            ):
                # Single-device CPU run (the CI smoke): the mesh needs
                # devices, but forcing virtual devices process-wide
                # skews the OTHER tiers' A/B budgets (the split
                # thread pool slows every dispatch). Re-run just this
                # tier in a subprocess with 8 forced virtual devices
                # (the test suite's conftest trick) and graft its
                # extra.multichip into this run. One process per chip:
                # CPU parents only (the guard above), child pinned to
                # the CPU through its environment.
                import subprocess
                import sys as _sys

                env = dict(
                    os.environ,
                    JAX_PLATFORMS="cpu",
                    TPFL_MULTICHIP_SUB="1",
                    XLA_FLAGS=(
                        os.environ.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                    ).strip(),
                )
                proc = subprocess.run(
                    [
                        _sys.executable,
                        os.path.abspath(__file__),
                        "--tiers",
                        "multichip",
                    ],
                    capture_output=True,
                    text=True,
                    env=env,
                    timeout=1800,
                )
                sub = json.loads(proc.stdout.splitlines()[-1])
                sub_extra = sub["extra"]
                if "multichip" in sub_extra:
                    extra["multichip"] = sub_extra["multichip"]
                    extra["multichip"]["subprocess_devices"] = 8
                else:
                    extra["multichip_error"] = sub_extra.get(
                        "multichip_error", "subprocess produced no tier"
                    )
                raise _MultichipDone()
            # CPU CI shares one host's cores across the forced virtual
            # devices — shrink the federation so the tier stays in the
            # smoke budget; the TPU run uses the sim1000 config.
            nM, nbM, bsM = (256, 1, 16) if cpu else (1000, 1, 32)
            hiddenM = (64,)
            R_WIN = 8 if cpu else 50
            rngM = np.random.default_rng(0)
            xsM = rngM.random((nM, nbM, bsM, 28, 28), np.float32)
            ysM = rngM.integers(0, 10, (nM, nbM, bsM)).astype(np.int32)
            wM = (rngM.random(nM) < 0.1).astype(np.float32)  # 10% partial

            def engine_for(d, n=nM, hidden=hiddenM):
                mesh = (
                    create_mesh({"nodes": d}, devices=jax.devices()[:d])
                    if d > 1
                    else None
                )
                return FederationEngine(
                    MLP(hidden_sizes=hidden), n, mesh=mesh,
                    learning_rate=0.1, seed=0,
                )

            def window_rps(d):
                """Rounds/sec at device count d: one R_WIN-round window
                per dispatch, best-of wall, shared RTT subtracted."""
                eng = engine_for(d)
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsM, ysM)
                w_d = eng.pad_weights(wM)
                fn = eng.program("plain", 1, R_WIN, 1)

                @jax.jit
                def window(p, xs, ys, w, v):
                    # Outer jit: the engine program's donation is inert
                    # inside the trace, so best_of_wall can reuse the
                    # argument buffers across repeats.
                    out = fn(p, {}, {}, {}, xs, ys, w, v)
                    return out[0], out[4]

                total, _ = profiling.best_of_wall(
                    window, (p, xs_d, ys_d, w_d, eng.valid)
                )
                per_round = max(total - (rtt or 0.0), 1e-9) / R_WIN
                return 1.0 / per_round

            mc: dict = {
                "devices": n_chips,
                "nodes": nM,
                "rounds_per_dispatch": R_WIN,
            }
            rps1 = window_rps(1)
            mc["rps_1dev"] = round(rps1, 2)
            if n_chips > 1:
                rpsD = window_rps(n_chips)
                mc["rps_ndev"] = round(rpsD, 2)
                mc["scaling_efficiency"] = round((rpsD / rps1) / n_chips, 3)
                mc["rps_by_devices"] = {
                    "1": round(rps1, 2), str(n_chips): round(rpsD, 2)
                }

            # Engine vs the legacy per-round path (VmapFederation's
            # single-round program through the shared timed-loop
            # methodology) — the engine must not lose on one device.
            fedL = VmapFederation(
                MLP(hidden_sizes=hiddenM), nM, learning_rate=0.1, seed=0
            )
            pL = fedL.init_params((28, 28))
            rfn = fedL._build_round()
            wL = jnp.asarray(wM)

            def stepL(c, xs, ys):
                p, _ = c
                p, losses = rfn(p, xs, ys, wL, 1)
                return p, losses

            perL, _ = _timed_loop(
                stepL,
                (pL, jnp.zeros((nM,), jnp.float32)),
                (jnp.asarray(xsM), jnp.asarray(ysM)),
                R_WIN * 2,
            )
            mc["legacy_rounds_per_sec"] = round(1.0 / perL, 2)
            mc["engine_vs_legacy"] = round(rps1 * perL, 3)

            # Live MFU gauge through the one CostModel path —
            # tpfl_mfu{program="engine"} (None off-TPU: no known peak).
            flopsM = profiling.cost_model.analytic_train_flops(
                MLP(hidden_sizes=hiddenM), (28, 28), nM * nbM * bsM
            )
            rps_use = mc.get("rps_ndev", rps1)
            if flopsM and peak:
                live = profiling.cost_model.record_round(
                    "engine", flopsM, 1.0 / max(rps_use, 1e-9),
                    n_chips=n_chips,
                )
                mc["round_tflops"] = round(flopsM / 1e12, 4)
                if live is not None:
                    mc["engine_mfu"] = round(live, 4)

            # Determinism: same seed at a FIXED device count must give
            # byte-identical global models across two from-scratch runs.
            def global_digest(d, rounds=3):
                eng = engine_for(d)
                p = eng.init_params((28, 28))
                xs_d, ys_d = eng.shard_data(xsM, ysM)
                p, _ = eng.run_rounds(
                    p, xs_d, ys_d, weights=wM, n_rounds=rounds
                )
                glob = jax.tree_util.tree_map(
                    lambda l: np.asarray(l[0]), eng.unpad(p)
                )
                return b"".join(
                    leaf.tobytes()
                    for leaf in jax.tree_util.tree_leaves(glob)
                )

            mc["determinism_byte_identical"] = (
                global_digest(n_chips) == global_digest(n_chips)
            )

            # Window-vs-sequential: the device-side multi-round loop
            # must equal N single-round dispatches (small config — the
            # invariant is shape-independent).
            nS = 32
            xsS, ysS = xsM[:nS], ysM[:nS]
            wS = wM[:nS]
            engA = engine_for(min(n_chips, 8), n=nS)
            pA = engA.init_params((28, 28))
            xa, ya = engA.shard_data(xsS, ysS)
            pA, _ = engA.run_rounds(pA, xa, ya, weights=wS, n_rounds=3)
            engB = engine_for(min(n_chips, 8), n=nS)
            pB = engB.init_params((28, 28))
            xb, yb = engB.shard_data(xsS, ysS)
            for _ in range(3):
                pB, _ = engB.round(pB, xb, yb, weights=wS)
            mc["window_matches_sequential"] = bool(
                all(
                    np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
                    for a, b in zip(
                        jax.tree_util.tree_leaves(pA),
                        jax.tree_util.tree_leaves(pB),
                    )
                )
            )

            # sim100k smoke: 100k registered clients, K sampled per
            # round — the ONLY persistent state is the global model;
            # per-round stacks are O(active).
            popl, K, R_pop = 100_000, 64, 3
            engK = engine_for(
                n_chips if K % max(n_chips, 1) == 0 else 1, n=K
            )
            glob = jax.tree_util.tree_map(
                lambda leaf: np.asarray(leaf[0]),
                engK.unpad(engK.init_params((28, 28))),
            )
            model_mb = sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree_util.tree_leaves(glob)
            ) / 1e6
            rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            for r in range(R_pop):
                idx = sample_participants(popl, K, seed=0, round=r)
                rr = np.random.default_rng(
                    np.random.SeedSequence([7, int(idx[0]), r])
                )
                xs_k = rr.random((K, 1, bsM, 28, 28), np.float32)
                ys_k = rr.integers(0, 10, (K, 1, bsM)).astype(np.int32)
                p = engK.broadcast_params(glob)
                xk, yk = engK.shard_data(xs_k, ys_k)
                p, _ = engK.round(p, xk, yk)
                glob = jax.tree_util.tree_map(
                    lambda leaf: np.asarray(leaf[0]), engK.unpad(p)
                )
            rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            # Linux ru_maxrss is KiB. O(population) state would be
            # population x model (~20 GB here); a few hundred MB of
            # peak growth is decisively O(active).
            delta_mb = max(0.0, (rss1 - rss0) / 1024.0)
            bound_mb = max(256.0, 64 * model_mb)
            mc["sim100k"] = {
                "population": popl,
                "active": K,
                "rounds": R_pop,
                "model_mb": round(model_mb, 3),
                "rss_delta_mb": round(delta_mb, 1),
                "rss_bounded": bool(delta_mb < bound_mb),
                "ok": True,
            }
            extra["multichip"] = mc
        except _MultichipDone:
            pass
        except Exception as e:
            extra["multichip_error"] = str(e)[:300]

    if "crosshost" in tiers:
        _crosshost_tier(extra)

    if "fleetobs" in tiers:
        _fleetobs_tier(extra)

    # Only quantitative anchor in the reference: 2-round MNIST e2e must
    # fit in 240 s (node_test.py:105) -> 0.00833 rounds/s floor.
    reference_floor_rounds_per_sec = 2.0 / 240.0

    doc = {
        "metric": "fedavg_cifar10_cnn_100nodes_samples_per_sec_per_chip",
        "value": round(samples_per_sec_chip, 1),
        "unit": "samples/s/chip",
        "vs_baseline": round(
            rounds_per_sec / reference_floor_rounds_per_sec, 1
        ),
        # Every printed result names the device it ran on.
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "extra": extra,
    }
    rc = 0
    if args.check:
        rc = _check_verdict(doc, args.check)
    print(json.dumps(doc))
    failed = _tier_errors(extra)
    if failed:
        # A tier that stored "<name>_error" did not run to its end: the
        # document above says why, the exit code says so.
        print(f"BENCH TIER FAILED: {', '.join(failed)}", file=sys.stderr)
        rc = rc or 1
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
