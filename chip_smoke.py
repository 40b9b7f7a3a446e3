#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpfl still starts on the chip.

One process, the entry points a user calls, one model of the zoo at
full width (depth and step counts are what is cut; weights and data
are random, made from ``--seed``). With no arguments it needs ONE TPU
chip and runs six phases:

- ``engine`` — ``VmapFederation`` -> ``FederationEngine``: ResNet-18
  (100 classes) x 16 nodes, 2 batches of 128 32x32x3 images, FedAvg
  with BatchNorm aux, two donating windows of 2 rounds, the second fed
  the first's outputs; then one window of the 100-node CNN with
  ``ENGINE_TELEMETRY`` and the quant8 ``ENGINE_WIRE_CODEC`` on (a
  separate program variant).
- ``gossip`` — the protocol path as the README starts it:
  ``tpfl.examples.digits``, in-memory transport, 4 nodes, 2 rounds,
  CNN: ``Node`` + ``JaxLearner`` + host-side FedAvg over real wire
  bytes.
- ``kernel`` — ``TransformerLM`` (dim 512, 8 heads, 4 layers) with the
  Pallas ``flash_attention`` at S=8192 bf16, 3 SGD steps; the kernels'
  outputs and q/k/v gradients (by name, and as ``blockwise_attention``
  runs them on a TPU) against a dense float32 softmax on the chip —
  and ``blockwise_attention`` with a band (window 1024, 8 query heads
  a key head of 128, 8192 tokens) against the same under the band's
  mask.
- ``experts`` — the expert layer's way back to tokens
  (``tpfl.parallel.moe_kernel``, the Pallas kernel a TPU runs) against
  the gather it replaces, on the head of ``mellum2_silo_8k``'s row
  buffer: 2 silos x 16384 tokens x 8 choices of 64 experts, 16 held,
  2304-wide bf16 rows, NaN past the groups; both timed (information).
- ``sync`` — INFORMATION for the benchmark PR: one window timed with
  ``jax.block_until_ready`` and with the scalar fetch, plus the
  dispatch round trip.
- ``cache`` — where the persistent compile cache lives and how many
  hits and misses this run saw.

``--chips 4`` (never what the driver runs) runs ONLY the multi-chip
phases and what they are compared with: the 100-node CNN on a
``nodes=4`` mesh and ``TransformerLM`` on ``nodes=2 x model=2`` (flash
ring on the ``model`` axis), each against one device.

Every phase prints one JSON line. A failed check, a non-finite loss or
any exception ends the run non-zero: nothing here catches a phase's
failure. The LAST line of stdout is, only when everything passed,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and a run that found no TPU never prints it (``require_chip`` raises
first). The script starts no child process: a chip belongs to one
process at a time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Any, Callable

#: The attention kernels against a dense S x S float32 softmax on the
#: chip: max |a - b| / max |b| for the output and each of dq / dk / dv.
#: Reason: bf16 keeps 8 mantissa bits (2^-8 ~ 0.4% per rounding); the
#: kernels round P and dS to bf16 before their matmuls and the results
#: to bf16 when they are written, the witness rounds nothing, so a few
#: roundings compound over the three matmuls. Measured on the v5e (PR
#: 30): 0.0074 with bf16 inputs, and 0.0070 with float32 inputs — the
#: MXU's default precision takes float32 operands in bf16 passes, in the
#: kernels as in XLA's own matmuls (on the CPU float32 agrees to 5e-7).
#: 2e-2 is under three times the reading; the values are printed.
FLASH_PARITY_TOL = 2e-2
#: The band of the banded comparison: Mellum 2's published window.
MELLUM_WINDOW = 1024

#: The expert layer's way back to tokens as the Pallas kernel against
#: the gather, float32 results of bf16 rows: max |a - b| / max |b|. A
#: product by 0 or 1 is exact, so the two differ by the ORDER of a
#: float32 sum of a token's at most 8 rows: a few units of 2^-24.
RETURN_PARITY_TOL = 1e-6

#: Mesh vs one device, mean last-round loss, relative: 2%, measured
#: 1e-5 to 2e-5 on the chip (ROADMAP S1, PR 21). Reduction order differs
#: (per-device partial sums + all-reduce), and on the 2D mesh the
#: attention is the flash ring against XLA blockwise, in bf16.
MESH_LOSS_RTOL = 2e-2

#: 1-D mesh vs one device, global CNN model after the window, absolute
#: (weights are O(0.1)). Same arithmetic, different f32 summation order
#: in the fold; a flipped bf16 rounding downstream moves a weight by
#: lr * (0.4% of a gradient term). Not byte-equal by construction.
MESH_PARAM_ATOL = 2e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


@dataclass(frozen=True)
class Sizes:
    """The sizes of a real run (defaults). Only the CPU walk-through in
    ``tests/test_chip_smoke.py`` shrinks them, to rehearse control flow
    without a chip; widths of the models are never parameters."""

    resnet_nodes: int = 16
    resnet_batches: int = 2
    cnn_nodes: int = 100
    cnn_batches: int = 4
    batch: int = 128
    window_rounds: int = 2
    sync_rounds: int = 4
    lm_seq: int = 8192
    lm_steps: int = 3
    parity_seq: int = 2048
    band_seq: int = 8192
    moe_tokens: int = 16384
    mesh_lm_seq: int = 2048
    mesh_lm_batch: int = 8


def compile_account() -> dict:
    """What the program's own set-up account
    (``profiling.observatory.setup_account``) says about compilation so
    far: backend compiles (loads from the persistent cache among them),
    the cache's hits and misses, and seconds spent tracing + lowering +
    loading + compiling, no second counted twice."""
    from tpfl.management import profiling

    account = profiling.observatory.setup_account()
    phases = account["phases"]
    return {
        "compiles": sum(
            phases[p]["events"] + phases[p]["nested_events"]
            for p in ("load", "compile")
        ),
        "hits": account["cache"]["hits"],
        "misses": account["cache"]["misses"],
        "seconds": sum(p["seconds"] for p in phases.values()),
    }


@dataclass
class Phase:
    """One phase's record: facts to print and the checks that held."""

    name: str
    facts: dict = field(default_factory=dict)
    checked: list = field(default_factory=list)

    def check(self, ok: Any, what: str) -> None:
        if not ok:
            raise SmokeFailure(f"[{self.name}] FAILED: {what}")
        self.checked.append(what)


def run_phases(
    phases: "list[tuple[str, Callable[[Phase], None]]]",
    device: dict,
    emit: Callable[[str], None] = print,
) -> None:
    """Run every phase in order, one JSON line each, then the result
    line. A phase that raises propagates: no later phase runs and the
    ``ok`` line is never printed."""
    for name, fn in phases:
        ph = Phase(name)
        t0 = time.perf_counter()
        before = compile_account()
        fn(ph)
        after = compile_account()
        emit(json.dumps({
            "phase": name,
            "seconds": round(time.perf_counter() - t0, 3),
            **ph.facts,
            "checked": ph.checked,
            "compile_seconds": round(after["seconds"] - before["seconds"], 3),
            "compiles": after["compiles"] - before["compiles"],
        }))
    emit(json.dumps({"ok": True, "device": device}))


# --- helpers -----------------------------------------------------------------


def _mean_loss(ph: Phase, losses: Any, what: str) -> float:
    import numpy as np

    arr = np.asarray(losses, np.float32)
    ph.check(bool(np.isfinite(arr).all()), f"{what}: losses finite")
    return float(arr.mean())


def _on_device(tree: Any, devices: set) -> bool:
    import jax

    return all(
        leaf.devices() == devices for leaf in jax.tree_util.tree_leaves(tree)
    )


def _peak_hbm(ph: Phase, dev: Any) -> int:
    """``peak_bytes_in_use`` of ``dev`` — a process-lifetime high-water
    mark, so each phase prints the peak SO FAR."""
    stats = dev.memory_stats()
    peak = int((stats or {}).get("peak_bytes_in_use", 0))
    ph.check(peak > 0, f"memory_stats() reports a peak on {dev}")
    return peak


@lru_cache(maxsize=2)
def _digit_draw(n: int, seed: int) -> tuple:
    """One seeded draw of ``n`` 32x32x3 rendered-digit images + labels.
    Cached: the dataset's column read costs ~4 ms an image, and three
    phases want the same draw. Callers only read (``np.resize`` copies)."""
    import numpy as np

    from tpfl.learning.dataset.rendered import rendered_color_digits

    split = rendered_color_digits(
        n_train=n, n_test=10, seed=seed
    ).get_split(True)
    return (
        np.asarray(split["image"], np.float32),
        np.asarray(split["label"], np.int32),
    )


def _node_batches(fed: Any, n: int, nb: int, bs: int, seed: int) -> tuple:
    """(xs, ys) placed by ``fed``: ``nb`` batches of ``bs`` bf16 images
    for each of ``n`` nodes. Requests past 4096 images tile one draw —
    device arrays stay full-size, set-up stays seconds."""
    import jax.numpy as jnp
    import numpy as np

    total = n * nb * bs
    x, y = _digit_draw(min(total, 4096), seed)
    return fed.shard_data(
        jnp.asarray(
            np.resize(x, (n, nb, bs, 32, 32, 3)), jnp.bfloat16
        ),
        np.resize(y, (n, nb, bs)),
    )


def _cnn_federation(sz: Sizes, seed: int, mesh: Any = None) -> tuple:
    """(fed, params, xs, ys): the 100-node CNN — 4 batches of 128
    bf16 images per node."""
    from tpfl.models import CNN
    from tpfl.parallel import VmapFederation

    fed = VmapFederation(
        CNN(out_channels=10), n_nodes=sz.cnn_nodes, mesh=mesh,
        learning_rate=0.1, seed=seed,
    )
    xs, ys = _node_batches(fed, sz.cnn_nodes, sz.cnn_batches, sz.batch, seed)
    return fed, fed.init_params((32, 32, 3)), xs, ys


# --- one chip ----------------------------------------------------------------


def phase_engine(ph: Phase, sz: Sizes, seed: int) -> None:
    import jax
    import numpy as np

    from tpfl.learning import compression
    from tpfl.management import profiling
    from tpfl.management.telemetry import metrics
    from tpfl.models import ResNet18
    from tpfl.parallel import VmapFederation
    from tpfl.settings import Settings

    dev = jax.devices()[0]
    rounds = sz.window_rounds
    snap = Settings.snapshot()
    try:
        # The CompileObservatory's signature probe is gated on this.
        Settings.PROFILING_ENABLED = True

        # ResNet-18(100) x 16: BASELINE config 3's shape, full width.
        n, nb, bs = sz.resnet_nodes, sz.resnet_batches, sz.batch
        # lr 0.02, not the zoo default 0.1: both windows must sit on the
        # FALLING part of the curve for "lower after window 2" to be a
        # check and not a coin toss. Ten of the hundred classes occur,
        # so the loss first drops from ln(100) towards ln(10); at 0.1
        # that is over inside window 1 and what follows is a plateau.
        fed = VmapFederation(
            ResNet18(out_channels=100), n_nodes=n, learning_rate=0.02,
            seed=seed,
        )
        params, aux = fed.init_state((32, 32, 3))
        xs, ys = _node_batches(fed, n, nb, bs, seed)
        donated = jax.tree_util.tree_leaves(params)[0]
        p1, a1, l1 = fed.run_rounds(
            params, xs, ys, aux=aux, n_rounds=rounds, donate=True
        )
        loss1 = _mean_loss(ph, l1, "resnet window 1")
        try:
            np.asarray(donated)
            raised = False
        except RuntimeError:
            raised = True
        ph.check(raised, "a read of a donated input raises")
        sigs = profiling.observatory.signature_counts()
        compiles = compile_account()["compiles"]
        p2, a2, l2 = fed.run_rounds(
            p1, xs, ys, aux=a1, n_rounds=rounds, donate=True
        )
        loss2 = _mean_loss(ph, l2, "resnet window 2")
        ph.check(
            profiling.observatory.signature_counts() == sigs,
            "CompileObservatory saw no new program signature in window 2",
        )
        ph.check(
            compile_account()["compiles"] == compiles,
            "jax compiled nothing in window 2",
        )
        ph.check(
            loss2 < loss1,
            f"resnet loss lower after window 2 ({loss2:.4f} < {loss1:.4f})",
        )
        ph.check(
            _on_device((p2, a2, l2), {dev}),
            f"every resnet output leaf lives on {dev}",
        )
        ph.facts["resnet18x16"] = {
            "nodes": n, "batches": nb, "batch": bs,
            "rounds_per_window": rounds,
            "loss_window1": round(loss1, 4), "loss_window2": round(loss2, 4),
            "peak_hbm_bytes": _peak_hbm(ph, dev),
            # Every counter the backend reports, once: which of them a
            # benchmark may call "peak HBM" is still open (PERF.md §7).
            "memory_stats": {
                k: int(v)
                for k, v in sorted((dev.memory_stats() or {}).items())
            },
        }
        del params, aux, p1, a1, p2, a2, xs, ys, fed

        # CNN x 100 with the telemetry carry and the quant8 exchange
        # codec: a separate program variant (engine._build_multi).
        Settings.ENGINE_TELEMETRY = True
        Settings.ENGINE_WIRE_CODEC = "quant8"
        fedc, pc, xs, ys = _cnn_federation(sz, seed)
        per_model = compression.wire_bytes_per_model(
            jax.tree_util.tree_map(
                lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), pc
            ),
            compression.QUANT8,
            float(Settings.WIRE_TOPK_FRAC),
        )
        pc, lc = fedc.run_rounds(pc, xs, ys, n_rounds=rounds, donate=True)
        loss_c = _mean_loss(ph, lc, "cnn telemetry+codec window")
        ph.check(
            any(
                ":obs:" in k and compression.codec_name(compression.QUANT8) in k
                for k in profiling.observatory.signature_counts()
            ),
            "the telemetry+quant8 program variant is the one that ran",
        )
        gauges = {
            k[0]: v for k, v in metrics.fold()["gauges"].items()
            if k[0].startswith("tpfl_engine_")
        }
        nc = sz.cnn_nodes
        ph.check(
            gauges.get("tpfl_engine_participation") == float(nc),
            f"telemetry carry: participation == {nc}",
        )
        ph.check(
            gauges.get("tpfl_engine_wire_bytes") == float(nc * per_model),
            "telemetry carry: wire_bytes == nodes x quant8 bytes per model",
        )
        ph.check(
            _on_device((pc, lc), {dev}),
            f"every cnn output leaf lives on {dev}",
        )
        ph.facts["cnn100_obs_quant8"] = {
            "nodes": nc, "batches": sz.cnn_batches, "batch": sz.batch,
            "rounds_per_window": rounds, "loss": round(loss_c, 4),
            "wire_bytes_per_round": int(nc * per_model),
            "peak_hbm_bytes": _peak_hbm(ph, dev),
        }
    finally:
        Settings.restore(snap)


def phase_gossip(ph: Phase, seed: int) -> None:
    import jax

    from tpfl.examples import digits as example
    from tpfl.settings import Settings
    from tpfl.utils import check_equal_models

    dev = jax.devices()[0]
    n_nodes, rounds = 4, 2
    snap = Settings.snapshot()
    try:
        nodes = example.digits(
            example.parse_args([
                "--nodes", str(n_nodes), "--rounds", str(rounds),
                # 3 local epochs: at 1 the CNN is still near 0.33 after
                # two rounds of 800 samples a node; at 3 it is near 0.88.
                "--epochs", "3", "--model", "cnn", "--protocol", "memory",
                "--seed", str(seed), "--no-show-metrics",
            ])
        )
    finally:
        Settings.restore(snap)
    ph.check(len(nodes) == n_nodes, f"{n_nodes} nodes ran")
    for nd in nodes:
        done = nd.learning_workflow.history.count("RoundFinishedStage")
        ph.check(
            done == rounds,
            f"{nd.addr} reached RoundFinished {done}/{rounds} times",
        )
    accs = [float(nd.learner.evaluate()["test_metric"]) for nd in nodes]
    ph.check(min(accs) > 0.5, f"accuracy > 0.5 on every node (min {min(accs):.3f})")
    check_equal_models(nodes, atol=1e-5)  # raises on disagreement
    ph.check(True, "cross-node model agreement (atol 1e-5)")
    ph.check(
        all(
            _on_device(nd.learner.get_model().get_parameters(), {dev})
            for nd in nodes
        ),
        f"every learner's params live on {dev}",
    )
    ph.facts.update(
        nodes=n_nodes, rounds=rounds, model="cnn", transport="memory",
        accuracy=[round(a, 4) for a in accs],
        peak_hbm_bytes=_peak_hbm(ph, dev),
    )


def lm_train_step(seq: int) -> tuple:
    """(lm, tx, step): ``TransformerLM`` (vocab 256, dim 512, 8 heads,
    4 layers) with the Pallas flash
    kernel on the zoo's ``attention_fn`` seam, and one SGD+momentum
    step ``step(params, opt_state, tokens) -> (params, opt_state,
    loss)``. ``interpret=False`` EXPLICITLY: this call cannot land on
    the emulator. Shared with ``tools/chip_rehearsal.py``."""
    import jax
    import optax

    from tpfl.models import TransformerLM
    from tpfl.parallel.flash_kernel import flash_attention

    lm = TransformerLM(
        vocab=256, dim=512, heads=8, n_layers=4, max_len=seq,
        attention_fn=partial(flash_attention, interpret=False),
    )
    tx = optax.sgd(1e-2, momentum=0.9)

    def step(p, o, t):
        def loss_of(pp):
            logits = lm.apply({"params": pp}, t, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], t[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(loss_of)(p)
        upd, o = tx.update(grads, o, p)
        return optax.apply_updates(p, upd), o, loss

    return lm, tx, step


def phase_kernel(ph: Phase, sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpfl.models import TransformerLM
    from tpfl.parallel.ring_attention import blockwise_attention

    dev = jax.devices()[0]
    seq = sz.lm_seq
    lm, tx, step = lm_train_step(seq)
    flash = lm.attention_fn  # flash_attention, interpret=False
    rng = np.random.default_rng(seed)
    # A seeded 64-token block repeated: learnable within three steps,
    # where uniform noise would leave "falling" to chance.
    toks = jnp.asarray(
        np.resize(rng.integers(0, 256, 64), (1, seq)), jnp.int32
    )
    params = lm.init(jax.random.PRNGKey(seed), toks[:, :128], train=False)[
        "params"
    ]
    opt = tx.init(params)
    compiled = jax.jit(step).lower(params, opt, toks).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    losses = []
    for _ in range(sz.lm_steps):
        params, opt, loss = compiled(params, opt, toks)
        losses.append(float(loss))
    ph.check(bool(np.isfinite(losses).all()), "LM losses finite")
    ph.check(
        losses[-1] < losses[0],
        f"LM loss falling ({losses[0]:.4f} -> {losses[-1]:.4f})",
    )
    ph.check(_on_device(params, {dev}), f"LM params live on {dev}")

    # The kernels against a witness that shares no code with them, at
    # the LM's head shape: on a TPU ``blockwise_attention`` runs the same
    # kernels as ``flash_attention`` (other blocks), so comparing those
    # two would compare Mosaic's output with itself.
    shape = (1, sz.parity_seq, 8, 64)
    q32, k32, v32, cot = (
        jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(4)
    )

    def dense(q, k, v, causal, window=None):
        """One S x S float32 softmax, XLA's autodiff for its gradients;
        grouped key heads repeated, a band as its mask."""
        assert causal
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = (jnp.repeat(x, q.shape[2] // k.shape[2], axis=2) for x in (k, v))
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, precision="highest"
        ) / np.sqrt(q.shape[-1])
        seen = jnp.tril(jnp.ones(scores.shape[-2:], bool))
        if window is not None:
            seen &= ~jnp.tril(seen, -window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

    def out_and_grads(fn, q, k, v, cot=cot):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32) * cot)

        out = jax.jit(partial(fn, causal=True))(q, k, v)
        return (out, *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))

    def rel_err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-9))

    errs = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        qkv = [x.astype(dtype) for x in (q32, k32, v32)]
        want = out_and_grads(dense, *qkv)
        for name, fn in (
            ("flash_attention", flash), ("blockwise_attention", blockwise_attention)
        ):
            errs[f"{name}.{jnp.dtype(dtype).name}"] = worst = {
                part: rel_err(got, ref)
                for part, got, ref in zip(
                    ("out", "dq", "dk", "dv"), out_and_grads(fn, *qkv), want
                )
            }
            ph.check(
                max(worst.values()) <= FLASH_PARITY_TOL,
                f"{name} ~ dense float32 softmax, {jnp.dtype(dtype).name} "
                f"inputs: out, dq, dk, dv within {FLASH_PARITY_TOL} "
                f"(worst {max(worst.values()):.3g})",
            )
    default_kernels = str(
        jax.make_jaxpr(partial(blockwise_attention, causal=True))(*qkv)
    ).count("pallas_call")

    # The band inside the kernels (PR 33) at Mellum 2's banded call: 8
    # query heads on a key head of 128, window 1024, bf16, the block left
    # to ``blockwise_attention`` — ONE key head, so that the witness's
    # [8, S, S] float32 scores and their gradients fit beside it.
    band_shapes = [(1, sz.band_seq, heads, 128) for heads in (8, 1, 1, 8)]
    band_qkv = [
        jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        for shape in band_shapes[:3]
    ]
    band_cot = jnp.asarray(rng.normal(size=band_shapes[3]), jnp.float32)
    banded = partial(blockwise_attention, window=MELLUM_WINDOW)
    errs["blockwise_attention.band.bfloat16"] = worst = {
        part: rel_err(got, ref)
        for part, got, ref in zip(
            ("out", "dq", "dk", "dv"),
            out_and_grads(banded, *band_qkv, cot=band_cot),
            out_and_grads(
                partial(dense, window=MELLUM_WINDOW), *band_qkv, cot=band_cot
            ),
        )
    }
    ph.check(
        max(worst.values()) <= FLASH_PARITY_TOL,
        f"blockwise_attention with window {MELLUM_WINDOW} ~ dense float32 "
        f"softmax under the band's mask, bfloat16 inputs, 8 query heads a "
        f"key head: out, dq, dk, dv within {FLASH_PARITY_TOL} "
        f"(worst {max(worst.values()):.3g})",
    )
    band_kernels = str(
        jax.make_jaxpr(partial(banded, causal=True))(*band_qkv)
    ).count("pallas_call")
    # Last, so that the CPU walk-through (tests/test_chip_smoke.py) runs
    # everything above before the checks a CPU cannot hold.
    ph.check(calls > 0, f"compiled LM step holds tpu_custom_call ({calls})")
    ph.check(
        default_kernels > 0,
        "blockwise_attention, the zoo's default, runs the kernels here",
    )
    ph.check(
        band_kernels > 0,
        "blockwise_attention with a band runs the kernels here",
    )
    ph.facts.update(
        lm={"dim": 512, "heads": 8, "layers": 4, "seq": seq, "dtype": "bf16"},
        # TransformerBlock's default (attention_fn=None) is
        # blockwise_attention, which runs the same Pallas kernels at its
        # own blocks here (checked above); the smoke's LM names them.
        attention_default=(
            "blockwise_attention (Pallas kernels)"
            if TransformerLM().attention_fn is None else "custom"
        ),
        attention_ran="flash_attention (Pallas, interpret=False)",
        tpu_custom_calls=calls,
        losses=[round(x, 4) for x in losses],
        parity_shape=list(shape),
        band_parity_shapes=[list(shape) for shape in band_shapes[:3]],
        parity_witness="dense S x S float32 softmax, XLA autodiff",
        parity_rel_err={
            who: {k: float(f"{e:.3g}") for k, e in worst.items()}
            for who, worst in errs.items()
        },
        peak_hbm_bytes=_peak_hbm(ph, dev),
    )


def phase_experts(ph: Phase, sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from tpfl.parallel import moe

    # Mellum 2's layer as its cell folds it: 2 silos, 8 choices of 64
    # experts a token, this chip's 16 held, rows of the hidden width.
    silos, tokens, k, held, experts, d = 2, sz.moe_tokens, 8, 16, 64, 2304
    groups = silos * held
    k_route, k_rows = jax.random.split(jax.random.PRNGKey(seed))
    _, expert = jax.lax.top_k(
        jax.random.normal(k_route, (silos, tokens, experts)), k
    )
    silo = jnp.arange(silos)[:, None, None]
    key = jnp.where(expert < held, expert + silo * held, groups)
    key = key.reshape(silos * tokens, k).astype(jnp.int32)
    order, pos, sizes = moe._plan(key, groups)
    live = int(sizes.sum())
    is_held = key < groups
    _, parts = moe._parts(
        order, pos.reshape(key.shape), sizes, is_held, (held, experts)
    )
    x = jax.ShapeDtypeStruct((silos * tokens, d), jnp.bfloat16)
    by_gather = (*parts[0], None)
    by_kernel = moe._with_runs(parts[:1], x, key, (held, experts))[0]
    n = parts[0][0].shape[0]
    # Past the groups a buffer holds whatever its kernels left: NaN here.
    rows = jnp.where(
        jnp.arange(n)[:, None] < live,
        jax.random.normal(k_rows, (n, d)), jnp.nan,
    ).astype(jnp.bfloat16)
    back = jax.jit(partial(moe._rows_to_tokens, k=k))

    def timed(part):
        out = jax.block_until_ready(back(rows, part))
        t0 = time.perf_counter()
        for _ in range(5):
            out = back(rows, part)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / 5 * 1e3

    want, gather_ms = timed(by_gather)
    ph.check(bool(jnp.isfinite(want).all()), "the gather's sums are finite")
    ph.check(live <= n, f"the live rows ({live}) lie in the head ({n})")
    got, kernel_ms = timed(by_kernel if by_kernel[-1] is not None else by_gather)
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    ph.check(
        err <= RETURN_PARITY_TOL,
        f"moe_rows_to_tokens ~ the gather back to tokens, bf16 rows, float32 "
        f"sums: within {RETURN_PARITY_TOL} (worst {err:.3g})",
    )
    ph.facts.update(
        shape={"tokens": silos * tokens, "k": k, "groups": groups,
               "rows": n, "live_rows": live, "d": d, "dtype": "bf16"},
        return_parity_rel_err=float(f"{err:.3g}"),
        gather_ms_per_call=round(gather_ms, 3),
        kernel_ms_per_call=round(kernel_ms, 3),
        kernel_live_gb_per_s=round(live * d * 2 / kernel_ms / 1e6, 1),
    )
    # Last: what a CPU cannot hold (tests/test_chip_smoke.py).
    ph.check(
        by_kernel[-1] is not None,
        "the way back to tokens runs the Pallas kernel here",
    )


def phase_sync(ph: Phase, sz: Sizes, seed: int) -> None:
    """Does ``block_until_ready`` block, and what does a dispatch cost,
    on THIS host? Information for the benchmark PR; the one assertion
    is that both syncs return only after the device is done."""
    import statistics

    import jax

    from tpfl.management import profiling

    fed, params, xs, ys = _cnn_federation(sz, seed)

    def window():
        return fed.run_rounds(
            params, xs, ys, n_rounds=sz.sync_rounds, donate=False
        )

    jax.block_until_ready(window())  # compile + warm
    bur, scalar, after = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        out = window()
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        profiling._sync_scalar(out)  # device already done: pure fetch
        t2 = time.perf_counter()
        bur.append(t1 - t0)
        after.append(t2 - t1)
        t0 = time.perf_counter()
        profiling._sync_scalar(window())
        scalar.append(time.perf_counter() - t0)
    t_bur, t_scalar = statistics.median(bur), statistics.median(scalar)
    ph.check(
        t_bur >= 0.5 * t_scalar and t_scalar >= 0.5 * t_bur,
        "block_until_ready and the scalar fetch both wait for the device "
        f"({t_bur * 1e3:.1f} ms vs {t_scalar * 1e3:.1f} ms)",
    )
    ph.facts.update(
        information_only=True,
        window={"model": "cnn", "nodes": sz.cnn_nodes, "rounds": sz.sync_rounds},
        block_until_ready_ms=round(t_bur * 1e3, 3),
        scalar_fetch_ms=round(t_scalar * 1e3, 3),
        scalar_fetch_after_block_ms=round(statistics.median(after) * 1e3, 3),
        dispatch_rtt_ms=round(profiling.measure_dispatch_rtt() * 1e3, 3),
    )


def phase_cache(ph: Phase, cache_dir: str) -> None:
    import os

    import jax

    from tpfl.management import profiling
    from tpfl.management.telemetry import metrics

    ph.check(
        jax.config.jax_compilation_cache_dir == cache_dir,
        "jax's cache directory is the resolver's",
    )
    entries = len(os.listdir(cache_dir))
    ph.check(entries > 0, f"entries exist under {cache_dir}")
    account = compile_account()
    ph.check(
        account["hits"] + account["misses"] > 0,
        "jax consulted the persistent cache",
    )
    warm = sum(
        v for k, v in metrics.fold()["counters"].items()
        if k[0] == "tpfl_compile_cache_warm_total"
    )
    ph.facts.update(
        directory=cache_dir,
        placed_by=(
            profiling.COMPILE_CACHE_ENV
            if os.environ.get(profiling.COMPILE_CACHE_ENV)
            else "<checkout>/.jax_cache"
        ),
        entries=entries,
        hits=account["hits"],
        misses=account["misses"],
        tpfl_compile_cache_warm_total=int(warm),
        compile_seconds_total=round(account["seconds"], 3),
    )


# --- four chips --------------------------------------------------------------


def _shard_facts(ph: Phase, tree: Any, devices: list, what: str) -> dict:
    """Every leaf sharded over all of ``devices``; bytes per device."""
    import jax

    per_device = {d.id: 0 for d in devices}
    total, spread = 0, True
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = leaf.addressable_shards
        spread = spread and {s.device for s in shards} == set(devices)
        total += leaf.nbytes
        for s in shards:
            per_device[s.device.id] += s.data.nbytes
    ph.check(
        spread,
        f"{what}: every parameter leaf has addressable_shards on "
        f"{len(devices)} distinct devices",
    )
    return {"total_bytes": total, "bytes_per_device": per_device}


def _memory_on_each(ph: Phase, devices: list, what: str) -> dict:
    stats = {d.id: (d.memory_stats() or {}) for d in devices}
    ph.check(
        all(s.get("bytes_in_use", 0) > 0 for s in stats.values()),
        f"{what}: memory_stats() shows bytes in use on EACH chip",
    )
    return {
        i: {
            "bytes_in_use": int(s["bytes_in_use"]),
            "peak_bytes_in_use": int(s.get("peak_bytes_in_use", 0)),
        }
        for i, s in stats.items()
    }


def _device_order(mesh: Any) -> list:
    """The order ``create_mesh`` produced, against each chip's coords
    (printed, not reordered: see ROADMAP S1)."""
    return [
        {"mesh_index": list(idx), "id": d.id, "coords": list(d.coords)}
        for idx, d in __import__("numpy").ndenumerate(mesh.devices)
    ]


def phase_mesh_1d(ph: Phase, sz: Sizes, seed: int) -> None:
    import jax
    import numpy as np

    from tpfl.parallel import create_mesh

    devices = jax.devices()[:4]
    mesh = create_mesh({"nodes": 4}, devices=devices)
    rounds = sz.window_rounds

    def run(m):
        fed, p, xs, ys = _cnn_federation(sz, seed, mesh=m)
        hlo = fed.engine.compiled_hlo(p, xs, ys, n_rounds=rounds) if m else ""
        p, losses = fed.run_rounds(p, xs, ys, n_rounds=rounds, donate=True)
        return fed, p, losses, hlo

    fed1, p1, l1, _ = run(None)
    loss1 = _mean_loss(ph, fed1.engine.unpad(l1), "cnn on one device")
    fed4, p4, l4, hlo = run(mesh)
    loss4 = _mean_loss(ph, fed4.engine.unpad(l4), "cnn on nodes=4")
    ph.check("all-reduce" in hlo, "nodes=4 program holds an all-reduce")
    ph.check(
        abs(loss4 - loss1) <= MESH_LOSS_RTOL * abs(loss1),
        f"loss parity with one device ({loss4:.5f} vs {loss1:.5f})",
    )
    diff = max(
        float(np.abs(np.asarray(a[0]) - np.asarray(b[0])).max())
        for a, b in zip(
            jax.tree_util.tree_leaves(p4), jax.tree_util.tree_leaves(p1)
        )
    )
    ph.check(
        diff <= MESH_PARAM_ATOL,
        f"global model allclose to one device (max diff {diff:.2e} "
        f"<= {MESH_PARAM_ATOL})",
    )
    shards = _shard_facts(ph, p4, devices, "nodes=4")
    ph.check(
        all(
            b * 4 == shards["total_bytes"]
            for b in shards["bytes_per_device"].values()
        ),
        "nodes=4: each device holds exactly 1/4 of the parameter bytes",
    )
    ph.facts.update(
        mesh={"nodes": 4}, device_order=_device_order(mesh),
        loss_one_device=round(loss1, 5), loss_mesh=round(loss4, 5),
        global_model_max_diff=float(f"{diff:.3g}"),
        all_reduces_in_hlo=hlo.count(" all-reduce("),
        params=shards, memory=_memory_on_each(ph, devices, "nodes=4"),
    )


def phase_mesh_2d(ph: Phase, sz: Sizes, seed: int) -> None:
    import jax
    import numpy as np

    from tpfl.models import TransformerLM
    from tpfl.parallel import FederationEngine, create_mesh

    devices = jax.devices()[:4]
    mesh = create_mesh({"nodes": 2, "model": 2}, devices=devices)
    seq, bs, rounds = sz.mesh_lm_seq, sz.mesh_lm_batch, sz.window_rounds
    module = TransformerLM(vocab=256, dim=512, heads=8, n_layers=4, max_len=seq)
    rng = np.random.default_rng(seed)
    xs = rng.integers(0, 256, (2, 1, bs, seq)).astype(np.int32)
    ys = rng.integers(0, 256, (2, 1, bs, seq)).astype(np.int32)

    def run(m):
        eng = FederationEngine(module, 2, mesh=m, seed=seed, learning_rate=0.05)
        p = eng.init_params((seq,))
        dx, dy = eng.shard_data(xs, ys)
        hlo = eng.compiled_hlo(p, dx, dy, n_rounds=rounds) if m else ""
        p, losses = eng.run_rounds(p, dx, dy, n_rounds=rounds, donate=True)
        return eng, p, losses, hlo

    eng1, _, l1, _ = run(None)
    loss1 = _mean_loss(ph, eng1.unpad(l1), "lm on one device")
    eng, p, l2, text = run(mesh)
    loss2 = _mean_loss(ph, eng.unpad(l2), "lm on nodes=2 x model=2")
    ph.check(eng.layout.name == "transformer", "the transformer SpecLayout")
    calls = text.count("tpu_custom_call")
    permutes = text.count(" collective-permute")
    ph.check(permutes > 0, f"K/V rotate the ring ({permutes} collective-permute)")
    ph.check(
        abs(loss2 - loss1) <= MESH_LOSS_RTOL * abs(loss1),
        f"loss parity with one device ({loss2:.5f} vs {loss1:.5f})",
    )
    shards = _shard_facts(ph, p, devices, "nodes=2 x model=2")
    share = max(shards["bytes_per_device"].values()) / shards["total_bytes"]
    ph.check(
        0.25 <= share < 0.5,
        f"nodes=2 x model=2: per-device share {share:.3f} is under the "
        "1/2 a node-only split gives (the layout shards the model axis)",
    )
    # Last: the one check virtual CPU devices cannot hold (the
    # walk-through in tests/test_chip_smoke.py runs all of the above).
    ph.check(
        calls > 0,
        f"model-axis ring took the flash inner ({calls} tpu_custom_call)",
    )
    ph.facts.update(
        mesh={"nodes": 2, "model": 2}, device_order=_device_order(mesh),
        lm={"dim": 512, "heads": 8, "layers": 4, "seq": seq, "batch": bs},
        loss_one_device=round(loss1, 5), loss_mesh=round(loss2, 5),
        tpu_custom_calls=calls, collective_permutes=permutes,
        params=shards, per_device_share=round(share, 4),
        memory=_memory_on_each(ph, devices, "nodes=2 x model=2"),
    )


# --- entry -------------------------------------------------------------------


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = ONLY the multi-chip phases and their one-device "
        "comparison (builder-run; fails with fewer than four chips)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from tpfl.management import profiling
    from tpfl.parallel import require_chip

    # First act: no TPU (or a kind the peaks table does not know, or too
    # few chips) raises here — nothing below ever runs on a CPU fallback.
    device = require_chip(min_count=args.chips)
    # Arms the cache and opens the set-up account compile_account reads.
    cache_dir = profiling.ensure_compile_cache()
    sz, seed = Sizes(), args.seed
    if args.chips == 4:
        phases = [
            ("mesh_nodes4", partial(phase_mesh_1d, sz=sz, seed=seed)),
            ("mesh_nodes2_model2", partial(phase_mesh_2d, sz=sz, seed=seed)),
        ]
    else:
        phases = [
            ("engine", partial(phase_engine, sz=sz, seed=seed)),
            ("gossip", partial(phase_gossip, seed=seed)),
            ("kernel", partial(phase_kernel, sz=sz, seed=seed)),
            ("experts", partial(phase_experts, sz=sz, seed=seed)),
            ("sync", partial(phase_sync, sz=sz, seed=seed)),
            ("cache", partial(phase_cache, cache_dir=cache_dir)),
        ]
    print(json.dumps({
        "chip_smoke": "start", "device": device, "seed": seed,
        "compile_cache": cache_dir,
        "phases": [name for name, _ in phases],
    }), flush=True)
    run_phases(phases, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
