"""Ask the TPU compiler about chip_smoke's programs WITHOUT a chip.

The on-chip-measurement guide's third rehearsal: compile the real
sizes for a *described* v5e (``topologies.get_topology_desc``) from a
CPU-only sandbox, so that what the chip's compiler refuses — a
mis-tiled kernel, too much VMEM, a program that does not fit 16 GB —
costs no chip time. Run before the first chip call of a PR that
touches the engine's round program, a Pallas kernel or the mesh:

    JAX_PLATFORMS=cpu python tools/chip_rehearsal.py            # all
    JAX_PLATFORMS=cpu python tools/chip_rehearsal.py flash ring # some
    JAX_PLATFORMS=cpu python tools/chip_rehearsal.py --ops 30 gpt2s_silo_1chip

``--ops N`` also lists each compiled program's N largest operations by
the compiler's own ``estimated_cycles`` (name, output shape, the tail
of its ``op_name``, cycles, and milliseconds at the clock that
``benchmark/peaks.json``'s peak implies) — a ranking of variants
between chip calls, NOT a time: the estimates ran 1.0x to 1.45x of
the measured time for matmuls and 3.5x for an element-wise pass
(PERF.md §5, PR 26). A name of
``BENCHMARK.json``'s ``workloads`` selects that cell's window program
(``benchmark/rehearse.py::window_program``).

Nothing executes: this says nothing about results or times, and a
compile that passes here is never reported as a chip run. The cheap
kernel cases also live in ``tests/test_chip_compile.py`` (tier-1);
the 20-second engine windows stay here.

The engine decides kernel-vs-emulator from ``jax.default_backend()``
(still "cpu" here), so this script steers the ONE seam that decision
goes through, ``tpfl.parallel.compat.on_tpu``.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpfl.parallel import compat  # noqa: E402

TOPOLOGY = "v5e:2x2"
#: Multiply-accumulates a cycle of one chip's matrix units (v5e: four
#: 128 x 128 MXUs); with the peaks table's FLOP/s it gives the clock
#: that turns ``estimated_cycles`` into milliseconds.
MXU_MACS_PER_CYCLE = {"TPU v5 lite": 4 * 128 * 128}
#: ``%name = <result shape> opcode(`` at the head of an HLO instruction.
OP_HEAD = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) [\w\-]+\(")
_OP_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LAYOUT = re.compile(r"\{[^{}]*\}")


def described_devices():
    """The four devices of a described (not attached) v5e 2x2 host."""
    return topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY
    ).devices


def force_chip_branch() -> None:
    """Make the Pallas entry points take their TPU branch while the
    backend is still the CPU (see module docstring)."""
    compat.on_tpu = lambda: True


def no_persistent_cache() -> None:
    """A described-topology executable is written to the persistent
    cache but cannot be read back without a chip (the next compile
    warns and recompiles) — keep it off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def clock_hz(device_kind: str) -> float:
    """The matrix units' clock implied by the benchmark's peaks table."""
    from benchmark import cells

    peak = cells.load_peaks(device_kind)["bf16_flops_per_s"]
    return peak / (2 * MXU_MACS_PER_CYCLE[device_kind])


def estimated_operations(text: str) -> list:
    """The operations of a compiled program's text that carry the TPU
    compiler's ``estimated_cycles`` — those that run as operations of
    their own: fusions, copies, reductions, not what sits inside a
    fusion — largest first. One inside a loop counts once, as it stands
    in the text; ``conditional`` branches and custom calls carry no
    estimate."""
    rows = []
    for line in text.splitlines():
        cycles = _OP_CYCLES.search(line)
        head = OP_HEAD.match(line)
        if not (cycles and head):
            continue
        op_name = _OP_NAME.search(line)
        rows.append({
            "name": head.group(1),
            "shape": _LAYOUT.sub("", head.group(2)),
            "op_name_tail": "/".join(
                op_name.group(1).split("/")[-3:]) if op_name else "",
            "cycles": int(cycles.group(1)),
        })
    return sorted(rows, key=lambda r: -r["cycles"])


def compile_report(
    name: str, jitted, args: tuple, ops: int = 0, hz: float = 0.0
) -> dict:
    """Lower + compile ``jitted`` for the described chip; one JSON-able
    line of what the compiler said (with ``ops``, its largest
    operations under ``estimated``)."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    dt = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "case": name,
        "compile_s": round(dt, 2),
        "tpu_custom_call": text.count("tpu_custom_call"),
        "all_reduce": len(re.findall(r" all-reduce(-start)?\(", text)),
        "collective_permute": len(
            re.findall(r" collective-permute(-start)?\(", text)
        ),
        "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
        **({"estimated": _estimate(text, ops, hz)} if ops else {}),
    }


def _estimate(text: str, ops: int, hz: float) -> dict:
    rows = estimated_operations(text)
    total = sum(r["cycles"] for r in rows)
    return {
        "operations": len(rows), "cycles": total,
        "est_ms": round(total / hz * 1e3, 2),
        "top": [
            dict(r, est_ms=round(r["cycles"] / hz * 1e3, 3)) for r in rows[:ops]
        ],
    }


def _sds(tree, sharding_of):
    """ShapeDtypeStructs carrying a sharding (``sharding_of`` is one
    sharding, or a matching pytree of them)."""
    if isinstance(sharding_of, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding_of),
            tree,
        )
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding_of,
    )


# --- kernels ---------------------------------------------------------------


def flash_case(shape, dtype, device) -> tuple:
    """(jitted fwd+bwd of the flash kernel, args) at ``shape``."""
    from tpfl.parallel.flash_kernel import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(device))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (x, x, x)


def block_attention_case(
    silos: int, q, k, v, device, block_size=None, window=None
) -> tuple:
    """(jitted fwd+bwd of ``blockwise_attention`` under the engine's vmap
    over ``silos``, args) for bf16 causal ``q`` / ``k`` / ``v`` shapes: on
    the TPU branch the block loop is two Pallas kernels, with a band
    (``window``) too."""
    from tpfl.parallel.ring_attention import blockwise_attention

    def loss(q, k, v):
        out = jax.vmap(
            lambda *x: blockwise_attention(
                *x, causal=True, block_size=block_size, window=window
            )
        )(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    sharding = SingleDeviceSharding(device)
    args = tuple(
        jax.ShapeDtypeStruct((silos, *shape), jnp.bfloat16, sharding=sharding)
        for shape in (q, k, v)
    )
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), args


def grouped_products_case(rows: int, groups: int, d: int, f: int, device) -> tuple:
    """(the six grouped products of one step of the held-experts layer
    — ``tpfl.parallel.moe``: gate-and-up and down forward, their two
    input and two weight gradients — jitted, args) on a row buffer of
    ``rows`` slots split over ``groups`` experts: on the TPU branch six
    Pallas grouped-matmul kernels."""
    from tpfl.parallel import moe

    def products(x, hidden, dy, d_gu, w_in, w_out, sizes):
        return (
            moe._gmm(x, w_in, sizes), moe._gmm(hidden, w_out, sizes),
            moe._gmm_nt(dy, w_out, sizes), moe._gmm_nt(d_gu, w_in, sizes),
            moe._gmm_tn(x, d_gu, sizes), moe._gmm_tn(hidden, dy, sizes),
        )

    sharding = SingleDeviceSharding(device)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding
    )
    args = (
        sds((rows, d)), sds((rows, f)), sds((rows, d)), sds((rows, 2 * f)),
        sds((groups, d, 2 * f)), sds((groups, f, d)),
        sds((groups,), jnp.int32),
    )
    return jax.jit(products), args


def rows_to_tokens_case(rows: int, d: int, tokens: int, groups: int, device) -> tuple:
    """(the expert layer's way back to tokens as its Pallas kernel —
    ``tpfl.parallel.moe_kernel`` — jitted, args) from a bf16 row buffer
    ``[rows, d]`` to ``tokens`` tokens over ``groups`` groups, at the
    token tile ``moe_kernel.tiles`` gives the shape."""
    from tpfl.parallel import moe_kernel

    tile = moe_kernel.tiles((rows, d), jnp.bfloat16, tokens, groups)
    assert tile, (rows, d, tokens, groups)
    sharding = SingleDeviceSharding(device)
    sds = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding
    )
    runs = sds((tokens // tile, groups))
    back = lambda rows, token, lo, hi: moe_kernel.rows_to_tokens(  # noqa: E731
        rows, token, lo, hi, tile, False
    )
    return jax.jit(back), (sds((rows, d), jnp.bfloat16), sds((rows,)), runs, runs)


def _expert_block_case(block, inputs: tuple, silos: int, device) -> tuple:
    """(jitted fwd+bwd — gradients of the parameters and every input —
    of ONE flax ``block`` with a ``moe_stats`` collection on ``inputs``
    (ShapeDtypeStructs) under the engine's vmap over ``silos``, args)."""
    variables = jax.eval_shape(
        lambda *x: block.init(jax.random.PRNGKey(0), *x), *inputs
    )
    params = variables["params"]
    stats = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), variables["moe_stats"]
    )

    def loss(params, *x):
        def one(p, *x):
            outs = block.apply({"params": p, "moe_stats": stats}, *x)
            return sum(
                jnp.sum(out.astype(jnp.float32) ** 2)
                for out in jax.tree_util.tree_leaves(outs)
            )

        return jnp.sum(jax.vmap(one)(params, *x))

    stacked = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((silos, *a.shape), a.dtype),
        (params, *inputs),
    )
    grads = jax.jit(jax.grad(loss, argnums=tuple(range(len(stacked)))))
    return grads, _sds(stacked, SingleDeviceSharding(device))


def zaya_block_case(silos: int, batch: int, seq: int, device) -> tuple:
    """ONE ``ZayaBlock`` at ZAYA1-8B's published widths — compressed
    convolutional attention, the MLP router with the router state of the
    layer below, 8 of 16 experts held (``_expert_block_case``)."""
    from tpfl.models.zaya import ZayaBlock

    block = ZayaBlock(
        heads=8, kv_heads=2, head_dim=128, conv_time=2, conv_head=2,
        rotary_fraction=0.5, theta=5e6, n_experts=16, expert_dim=2048,
        router_dim=256, held_experts=8, first_expert=0, norm_eps=1e-5, out_std=0.001,
        compute_dtype=jnp.bfloat16,
    )
    x = jax.ShapeDtypeStruct((batch, seq, 2048), jnp.bfloat16)
    z = jax.ShapeDtypeStruct((batch, seq, 256), jnp.float32)
    return _expert_block_case(block, (x, z), silos, device)


def mellum_block_case(silos: int, batch: int, seq: int, device) -> tuple:
    """ONE banded ``MellumBlock`` at Mellum 2's published widths —
    rotary positions, 8 query heads a key head, a window of 1024, 16 of
    64 experts held — recomputed in the backward pass (``nn.remat``, as
    ``MellumLM`` runs it; ``_expert_block_case``)."""
    import flax.linen as nn

    from tpfl.models.mellum import MellumBlock, MellumLM

    block = nn.remat(MellumBlock)(
        full=False, heads=32, kv_heads=4, head_dim=128, window=1024,
        theta=500000.0, yarn=MellumLM.yarn, n_experts=64, top_k=8,
        expert_dim=896, held_experts=16, first_expert=0, norm_eps=1e-6,
        compute_dtype=jnp.bfloat16,
    )
    x = jax.ShapeDtypeStruct((batch, seq, 2304), jnp.bfloat16)
    return _expert_block_case(block, (x,), silos, device)


def scan_case(shape, states: int, silos: int, device) -> tuple:
    """(jitted fwd+bwd of the selective scan's Pallas kernels under the
    engine's vmap over ``silos``, args) for ``shape = (batch, S, D)``."""
    from tpfl.parallel.selective_scan import selective_scan

    def loss(c, delta, a, bmat, cmat, dskip):
        out = jax.vmap(
            lambda *x: selective_scan(*x, impl="kernel")
        )(c, delta, a, bmat, cmat, dskip)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    sharding = SingleDeviceSharding(device)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        (silos, *shape), dtype, sharding=sharding
    )
    d = shape[-1]
    small = (*shape[:-1], states)
    args = (
        sds(shape, jnp.bfloat16), sds(shape, jnp.float32),
        sds((d, states), jnp.float32), sds(small, jnp.bfloat16),
        sds(small, jnp.bfloat16), sds((d,), jnp.float32),
    )
    return jax.jit(jax.grad(loss, argnums=tuple(range(6)))), args


def ring_case(devices, seq: int = 8192) -> tuple:
    """(jitted fwd+bwd of ring attention's flash inner over a 4-device
    ``sp`` mesh, args)."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec

    from tpfl.parallel.mesh import create_mesh
    from tpfl.parallel.ring_attention import ring_attention

    mesh = create_mesh({"sp": len(devices)}, devices=devices)
    spec = PartitionSpec(None, "sp", None, None)
    ring = compat.shard_map(
        partial(
            ring_attention, axis_name="sp", causal=True, impl="flash",
            interpret=False,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )

    def loss(q, k, v):
        return jnp.sum(ring(q, k, v).astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(
        (1, seq, 8, 64), jnp.bfloat16, sharding=NamedSharding(mesh, spec)
    )
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (x, x, x)


def lm_step_case(seq: int, device) -> tuple:
    """(chip_smoke's jitted TransformerLM + flash SGD step, args)."""
    import chip_smoke

    lm, tx, step = chip_smoke.lm_train_step(seq)
    toks = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), toks[:, :128], train=False)
    )["params"]
    opt = jax.eval_shape(tx.init, params)
    sh = SingleDeviceSharding(device)
    return jax.jit(step), (_sds(params, sh), _sds(opt, sh), _sds(toks, sh))


# --- engine windows ----------------------------------------------------------


def engine_case(
    module, n_nodes: int, batch_shape: tuple, input_shape: tuple,
    devices, mesh_axes: "dict | None" = None, n_rounds: int = 2,
    algorithm: str = "fedavg", telemetry: bool = False, codec: int = 0,
    x_dtype=jnp.bfloat16, y_tail: tuple = (),
) -> tuple:
    """(the engine's own jitted DONATING window program, abstract args)
    for ``module`` x ``n_nodes`` — built by ``_build_program`` exactly
    as ``dispatch_window`` fetches it, lowered on shapes (a described
    device holds no arrays)."""
    from tpfl.parallel import FederationEngine
    from tpfl.parallel.mesh import (
        create_mesh,
        federation_sharding,
        global_model_shardings,
        replicated,
        stacked_model_shardings,
    )

    mesh = create_mesh(mesh_axes, devices=devices) if mesh_axes else None
    eng = FederationEngine(module, n_nodes, mesh=mesh, algorithm=algorithm)
    dummy = jnp.zeros(
        (1, *input_shape), getattr(module, "input_dtype", jnp.float32)
    )
    variables = jax.eval_shape(
        lambda: eng.module.init(jax.random.PRNGKey(0), dummy, train=False)
    )
    n = eng.padded_nodes

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((n, *x.shape), x.dtype), tree
        )

    params = stack(variables["params"])
    aux = stack({k: v for k, v in variables.items() if k != "params"})
    kind = "scaffold" if algorithm == "scaffold" else ("aux" if aux else "plain")
    c_locals = params if kind == "scaffold" else {}
    c_global = variables["params"] if kind == "scaffold" else {}

    def state_sh(tree, stacked=True):
        """Where the engine places model state: as _shard_state (node-
        stacked) / _shard_global (c_global) do on this mesh."""
        if mesh is None:
            return SingleDeviceSharding(devices[0])
        if eng.model_axes > 1:
            per_leaf = (
                stacked_model_shardings if stacked else global_model_shardings
            )
            return per_leaf(mesh, tree, eng.layout)
        return federation_sharding(mesh) if stacked else replicated(mesh)

    node_sh = state_sh(None) if eng.model_axes <= 1 else federation_sharding(mesh)
    xs = jax.ShapeDtypeStruct((n, *batch_shape, *input_shape), x_dtype)
    ys = jax.ShapeDtypeStruct((n, *batch_shape, *y_tail), jnp.int32)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    args = (
        _sds(params, state_sh(params)),
        _sds(c_locals, state_sh(c_locals)),
        _sds(c_global, state_sh(c_global, stacked=False)),
        _sds(aux, state_sh(aux)),
        _sds(xs, node_sh), _sds(ys, node_sh),
        _sds(vec, node_sh), _sds(vec, node_sh),
    )
    if eng.model_axes > 1:
        # What _prepare_args stashes for the 2D builder's explicit
        # in/out shardings (donation aliasing).
        eng._arg_shardings = tuple(
            jax.tree_util.tree_map(lambda x: x.sharding, a) for a in args
        )
    fn = eng._build_program(
        kind, 1, n_rounds, 1, True, telemetry, 0, codec, 0.05,
        eng.model_axes, eng.layout.name,
    )
    return fn, args


def cases(devices) -> dict:
    """name -> thunk returning (jitted, args). Cheap ones first."""
    from tpfl.learning.compression import resolve_engine_codec
    from tpfl.models import CNN, ResNet18, TransformerLM

    d0 = devices[0]
    bf16, f32 = jnp.bfloat16, jnp.float32
    q8 = resolve_engine_codec("quant8")
    cnn = lambda **kw: engine_case(  # noqa: E731
        CNN(out_channels=10), 100, (4, 128), (32, 32, 3), devices, **kw
    )
    return {
        "flash_8k": lambda: flash_case((1, 8192, 8, 64), bf16, d0),
        "flash_32k": lambda: flash_case((1, 32768, 8, 64), bf16, d0),
        "flash_4k_h16_d128": lambda: flash_case((2, 4096, 16, 128), bf16, d0),
        "flash_2k_f32": lambda: flash_case((1, 2048, 8, 64), f32, d0),
        # The edges of what ``flash_kernel.tiles`` admits: the largest
        # tile ([1024, 1024]) with a head block's resident dq (float32
        # scratch + double-buffered output block) at 64 and 63 MiB.
        "flash_64k_d128": lambda: flash_case((1, 65536, 8, 128), bf16, d0),
        "flash_40k_d128_f32": lambda: flash_case((1, 40960, 8, 128), f32, d0),
        "ring_flash_sp4": lambda: ring_case(devices),
        # One attention call of the benchmark's LM cells: GPT-2's 4 x
        # 1024 tokens a silo at four silos, SambaY's one 8192-token
        # sequence at two (grouped heads, a value width of its own).
        "block_attention_gpt2_x4": lambda: block_attention_case(
            4, *[(4, 1024, 12, 64)] * 3, d0
        ),
        "block_attention_sambay_x2": lambda: block_attention_case(
            2, (1, 8192, 40, 64), (1, 8192, 20, 64), (1, 8192, 20, 128), d0
        ),
        # Mellum 2's full-attention layer in its cell: two 8192-token
        # sequences a silo, 32 query heads on 4 key heads of 128 — a key
        # head's block is 8 x 256 rows tall, the resident dq exactly
        # ``_DQ_BYTES`` — and the experts' grouped products on the head
        # of the row buffer (three eighths of 2 silos x 16384 tokens x 8
        # choices), 2 x 16 experts.
        "block_attention_mellum_x2": lambda: block_attention_case(
            2, (2, 8192, 32, 128), (2, 8192, 4, 128), (2, 8192, 4, 128), d0,
            block_size=256,
        ),
        # The banded layers of both models, the block left to
        # ``blockwise_attention``'s own rule: Mellum 2's window of 1024
        # (block 256: five pairs a query block, two of them masked) and
        # SambaY's sliding window of 512 (block 512: two pairs, both
        # masked; two query heads a key head, a value twice as wide).
        "block_attention_mellum_band_x2": lambda: block_attention_case(
            2, (2, 8192, 32, 128), (2, 8192, 4, 128), (2, 8192, 4, 128), d0,
            window=1024,
        ),
        "block_attention_sambay_band_x2": lambda: block_attention_case(
            2, (1, 8192, 40, 64), (1, 8192, 20, 64), (1, 8192, 20, 128), d0,
            window=512,
        ),
        "grouped_products_mellum_x2": lambda: grouped_products_case(
            98304, 32, 2304, 896, d0
        ),
        # ... and ONE whole banded block as the cell runs it (rotary, the
        # kernels on grouped heads as lanes, the expert layer, recomputed
        # in the backward pass): what lies between the projections and
        # the kernels is in its compiled text.
        "mellum_block_x2": lambda: mellum_block_case(2, 2, 8192, d0),
        # ... and the way back to the 2 x 16384 tokens from that head and
        # from the buffer's rest (``moe_kernel``, PR 35: 64 token tiles of
        # 512 over 32 groups).
        "rows_to_tokens_mellum_x2": lambda: rows_to_tokens_case(
            98304, 2304, 32768, 32, d0
        ),
        "rows_to_tokens_mellum_rest_x2": lambda: rows_to_tokens_case(
            163840, 2304, 32768, 32, d0
        ),
        # ZAYA1-8B's cell: the attention call inside the latent (2 key
        # heads, 4 query heads a key head, d = 128: block 512 exactly at
        # ``_TILE_BYTES``), the experts' grouped products on the head of
        # the row buffer (three quarters of 2 silos x 16384 tokens x 1
        # choice, 2 x 8 experts of 2048 x 4096: tiles of 1024), and one
        # whole block (the convolutions, the router MLP and its state).
        "block_attention_zaya_x2": lambda: block_attention_case(
            2, (2, 8192, 8, 128), (2, 8192, 2, 128), (2, 8192, 2, 128), d0
        ),
        "grouped_products_zaya_x2": lambda: grouped_products_case(
            24576, 16, 2048, 2048, d0
        ),
        "zaya_block_x2": lambda: zaya_block_case(2, 1, 8192, d0),
        # The Mamba layer of the benchmark's SambaY cell: one 8192-token
        # sequence a silo, 5120 channels x 16 states, two silos vmapped.
        "ssm_scan_8k_x2": lambda: scan_case((1, 8192, 5120), 16, 2, d0),
        "lm_step_8k": lambda: lm_step_case(8192, d0),
        "engine_cnn100": lambda: cnn(n_rounds=4),
        "engine_cnn100_obs_q8": lambda: cnn(telemetry=True, codec=q8),
        "engine_cnn100_scaffold": lambda: cnn(algorithm="scaffold"),
        "engine_resnet18x16": lambda: engine_case(
            ResNet18(out_channels=100), 16, (2, 128), (32, 32, 3), devices
        ),
        "engine_cnn100_nodes4": lambda: cnn(mesh_axes={"nodes": 4}),
        # GPT-2 small's published width, heads, context and vocabulary,
        # ONE block, 2 silos: the head and its loss at their real
        # shapes in seconds (the benchmark's cell takes half a minute).
        "engine_gpt2_head_x2": lambda: engine_case(
            TransformerLM(
                vocab=50257, dim=768, heads=12, n_layers=1, max_len=1024
            ),
            2, (1, 4), (1024,), devices, n_rounds=1, x_dtype=jnp.int32,
            y_tail=(1024,),
        ),
        "engine_lm_nodes2_model2": lambda: engine_case(
            TransformerLM(
                vocab=256, dim=512, heads=8, n_layers=4, max_len=2048
            ),
            2, (1, 8), (2048,), devices,
            mesh_axes={"nodes": 2, "model": 2}, x_dtype=jnp.int32,
            y_tail=(2048,),
        ),
    }


def cell_cases(devices, names: list) -> dict:
    """The window programs of the ``BENCHMARK.json`` cells among
    ``names``, as the benchmark's own rehearsal builds them."""
    from benchmark import cells, rehearse

    known = {w["name"] for w in cells.load_benchmark()["workloads"]}
    return {
        name: lambda name=name: rehearse.window_program(
            cells.load_cell(name), list(devices)
        )
        for name in names if name in known
    }


def main(argv: list[str]) -> int:
    ops = 0
    if "--ops" in argv:
        at = argv.index("--ops")
        ops, argv = int(argv[at + 1]), argv[:at] + argv[at + 2:]
    devices = described_devices()
    force_chip_branch()
    no_persistent_cache()
    hz = clock_hz(devices[0].device_kind) if ops else 0.0
    table = {**cases(devices), **cell_cases(devices, argv)}
    wanted = [k for k in table if not argv or any(a in k for a in argv)]
    print(json.dumps({
        "topology": TOPOLOGY, "device_kind": devices[0].device_kind,
        "note": "compile-only rehearsal: NOT a chip run, no times or results",
    }))
    failed = 0
    for name in wanted:
        try:
            fn, args = table[name]()
            report = compile_report(name, fn, args, ops, hz)
            top = report.get("estimated", {}).pop("top", [])
            print(json.dumps(report), flush=True)
            for row in top:
                print(json.dumps(row), flush=True)
        except Exception as e:  # report every refusal, then fail
            failed += 1
            msg = str(e).strip().splitlines()
            print(json.dumps({
                "case": name, "refused": type(e).__name__,
                "why": " | ".join(msg[:6])[:1200],
            }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
