"""The cell ``zaya1_silo_8k`` and what PR 34 added to the benchmark: its
files load and state the cut, parameter count and multiplies are the hand
counts, one federated round through the engine meets the plain reference
(the routers' loads as ``aux`` too) at toy widths on the CPU, a lower
precision breaks the check's limits, the cell runs end to end at toy
size, and the seven new readers read a hand-built trace and stay silent
on an empty one."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, harness, scope_paths
from toy_zaya1 import CELL, toy_cell

NEW_READERS = (
    "cca_ms_per_round", "cca_mix_ms_per_round", "cca_attention_kernel_share_pct",
    "top1_moe_ms_per_round", "top1_routing_ms_per_round",
    "top1_experts_ms_per_round", "top1_experts_roofline_pct",
)


def test_cell_files_load_and_state_the_cut():
    cell = cells.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1
    # The traffic file is Mellum 2's, as it is.
    assert traffic == cells.load_cell("mellum2_silo_8k").traffic
    assert (traffic["nodes"], traffic["batch"], traffic["seq"]) == (2, 2, 8192)
    # Published widths, unchanged; depth, experts held and vocabulary
    # reduced and said so.
    published = {
        "hidden_size": 2048, "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 128, "moe_intermediate_size": 2048, "num_experts_per_tok": 1,
        "router_hidden_size": 256, "cca_time0": 2, "cca_time1": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "lm_head_bias": False,
        "attention_bias": False, "hidden_act": "silu", "sliding_window": None,
        "max_position_embeddings": 131072, "model_type": "zaya",
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        "hybrid": {
            "partial_rotary_factor": 0.5, "rope_theta": 5000000,
            "rope_type": "default",
        },
        "hybrid_sliding": {
            "partial_rotary_factor": 0.5, "rope_theta": 10000,
            "rope_type": "default",
        },
        "rope_type": "default",
    }
    assert cfg["layer_types"] == ["hybrid"] * 40
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = next(
        c for c in cells.load_benchmark()["configs"] if c["name"] == cfg["name"]
    )
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{cfg['name']}.json"
    assert entry["source"] == cfg["source"]
    assert cfg["layers"] == [0, 1, 2, 3, 4] and cfg["num_hidden_layers"] == 5
    assert cfg["published"] == {
        "num_hidden_layers": 40, "num_experts": 16, "vocab_size": 262272,
    }
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["experts_held"] == {
        "first": 0, "count": cfg["num_experts"], "router_width": 16,
        "experts_per_token": 1,
    }
    assert cfg["num_experts"] * 2 == cfg["published"]["num_experts"]
    assert "TWO chips" in cfg["deployment"] and "PARTIAL" in cfg["deployment"]
    assert "GB" in cfg["what_set_the_cut"]
    for key in (
        "norm_placement", "residual_scales", "convolutions", "qk_mean",
        "qk_norm", "rotary", "value_shift", "router", "balancing_bias",
        "skip_expert", "load_balancing_loss", "initialisation", "data",
    ):
        assert cfg["assumed"][key], key
    workload = next(
        w for w in cells.load_benchmark()["workloads"] if w["name"] == CELL
    )
    assert "HALF" in workload["why"] and "TWICE" in workload["why"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(names) and "mfu_device_pct" in names
    # Mellum 2's expert metrics list their cells by name: not read here.
    assert not {"moe_ms_per_round", "block_attention_ms_per_round"} & set(names)
    assert {m["name"] for m in cell.end_to_end} == {
        "rounds_per_s", "peak_hbm_gb", "loss_at_k", "setup_s",
    }
    assert set(cell.model.CHECK_TOLERANCES) == {"loss", "update", "aux"}


def test_configuration_counts_its_parameters_and_multiplies():
    cell = cells.load_cell(CELL)
    cfg = cell.config
    module = cell.model.build_module(cfg)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
    )
    params = shapes["params"]
    assert count(params) == cfg["parameters"] == 601_685_775
    assert {k: count(v) for k, v in params.items()} == cfg["parameters_by_part"]
    layer = params["layer_4"]
    attention, moe = layer["attention"], layer["moe"]
    projections = sum(count(attention[f"{n}_proj"]) for n in "qkvo")
    convolutions = sum(
        count(attention[f"{n}_conv_{kind}"]) for n in "qk" for kind in ("time", "head")
    )
    experts = count(moe["gate_up_proj"]) + count(moe["down_proj"])
    assert {
        "cca_projections": projections, "cca_convolutions": convolutions,
        "key_temperature": count(attention["k_temperature"]),
        "router": count(moe) - experts,
        "norms": count(layer["norm_attention"]) + count(layer["norm_moe"]),
        "residual_scales": sum(
            count(v) for k, v in layer.items() if k.endswith("_scale")
        ),
        "experts": experts, "one_expert": 3 * 2048 * 2048,
    } == cfg["parameters_of_a_layer"]
    # ISSUE 34's hand count, leaf by leaf.
    assert projections == 2048 * (1024 + 256 + 256) + 1024 * 2048 == 5_242_880
    assert convolutions == 2 * 1280 + (8 + 2) * 2 * 128 * 128 == 330_240
    assert count(moe) - experts == 524_288 + 2 * 65_536 + 4_096 + 1 + 256
    assert count(layer) == 106_908_419
    assert moe["gate_up_proj"].shape == (8, 2048, 4096)
    assert moe["down_proj"].shape == (8, 2048, 2048)
    assert moe["router_out"].shape == (256, 16)
    assert attention["q_conv_head"].shape == (8, 2, 128, 128)
    assert "head" not in params  # tied: the embedding, counted once
    # The counter the engine carries: a load per published expert a layer.
    stats = shapes["moe_stats"]
    assert sorted(stats) == [f"layer_{i}" for i in range(5)]
    assert stats["layer_0"]["moe"]["moe_load"].shape == (16,)
    assert stats["layer_0"]["moe"]["balance_bias"].shape == (16,)
    # The hand count at S = 8192: a token sends 1 x 8 / 16 = half a row
    # to the held experts; a query sees 4096.5 keys on average.
    d, f, s = 2048, 2048, 8192
    per_layer = (
        d * 1536 + 1024 * d  # latent projections and back
        + 2 * 1280 + 2 * 128 * 1280  # depthwise and per-head taps
        + 2 * 1024 * (s + 1) / 2  # scores and values
        + d * 256 + 2 * 256 * 256 + 256 * 16  # the router MLP
        + 0.5 * 3 * d * f  # half a row through one SwiGLU expert
    )
    assert per_layer == 20_913_664
    want = 5 * per_layer + d * 32784
    assert cell.model.fwd_mults_per_sample(cfg, cell.traffic) == int(want) == 171_709_952
    # The head's share of the multiplies, as the cell's ``why`` says.
    assert round(100 * d * 32784 / want) == 39
    # The experts' roofline count: 6 x 3 d f x (32,768 tokens x 1/2 row) x 5.
    assert cell.model.expert_flops_per_round(cfg, cell.traffic) == (
        6 * 3 * d * f * 16384 * 5
    )


def test_data_is_a_markov_source_over_a_quarter_of_the_slice():
    """The cell's own alphabet: 8,192 ids spread over the 32,784-row
    slice (every fourth id), each followed by one of four fixed
    successors; ys is xs one token on; the same key, the same tokens."""
    cell = cells.load_cell(CELL)
    traffic = dict(cell.traffic, seq=4096)
    make = jax.jit(lambda key: cell.model.make_data(key, cell.config, traffic))
    xs, ys = map(np.asarray, make(jax.random.PRNGKey(2147484001)))
    assert xs.shape == ys.shape == (2, 1, 2, 4096) and xs.dtype == np.int32
    assert (xs[..., 1:] == ys[..., :-1]).all()
    stride = cell.config["vocab_size"] // cell.model.ACTIVE_TOKENS
    assert cell.model.ACTIVE_TOKENS == 8192 and stride == 4
    assert (xs % stride == 0).all() and xs.max() < stride * 8192
    assert len(np.unique(xs)) > 6000
    successors = {}
    for cur, nxt in zip(xs.reshape(-1) // stride, ys.reshape(-1) // stride):
        successors.setdefault(int(cur), set()).add(int(nxt))
    assert max(map(len, successors.values())) <= 4
    again, _ = make(jax.random.PRNGKey(2147484001))
    assert (np.asarray(again) == xs).all()


def test_engine_round_meets_reference_round():
    """One federated round, 2 nodes, uneven weights, the harness's own
    check (the comparison that decides ``correct`` on the chip) — of the
    share the cell holds and of the other chip's."""
    for first in (0, 4):
        check = harness.check_against_reference(
            toy_cell(first), 3, jax.devices()[:1]
        )
        assert check["agrees"] and check["nodes"] == 2
        assert check["loss_rel_err"] < 1e-5 and check["update_rel_err"] < 1e-3
        assert check["aux_rel_err"] < 1e-5
        assert len(set(check["losses_reference"])) == 2


@pytest.mark.parametrize("what", ["bf16_parameters", "bf16_router"])
def test_a_lower_precision_against_the_check(what, monkeypatch):
    """The same toy round with bfloat16 where the configuration states
    float32. Parameters STORED in bf16 break the update's limit (a step
    of lr x gradient is under half a bf16 ulp of most weights). A router
    MLP whose products are rounded to bf16 reads hundreds of times the
    update error of the run as stated — and stays inside every limit AT
    TOY WIDTHS: 64 token types route by identity and none sits near a
    tie, so no choice flips and ``aux`` reads 0. What it reads at the
    published widths, where choices do flip, is in the model file beside
    ``CHECK_TOLERANCES`` (measured on the chip)."""
    cell = toy_cell()
    tol = cell.model.CHECK_TOLERANCES
    as_stated = harness.check_against_reference(cell, 3, jax.devices()[:1])
    if what == "bf16_parameters":
        real_init = harness.init_state

        def init_in_bf16(*args):
            params, aux = real_init(*args)
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), params
            ), aux

        monkeypatch.setattr(harness, "init_state", init_in_bf16)
    else:
        from tpfl.models import zaya

        real_dot = jnp.dot

        def rounded_dot(a, b, precision=None):
            if precision is zaya.HIGHEST:  # the router's products
                a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
                return real_dot(a, b).astype(jnp.float32)
            return real_dot(a, b, precision=precision)

        monkeypatch.setattr(zaya.jnp, "dot", rounded_dot)
    check = harness.check_against_reference(cell, 3, jax.devices()[:1])
    errors = {
        "loss": check["loss_rel_err"], "update": check["update_rel_err"],
        "aux": check["aux_rel_err"],
    }
    broken = [name for name, err in errors.items() if err > tol[name]]
    assert as_stated["agrees"] and as_stated["update_rel_err"] < 1e-5
    assert errors["update"] > 100 * as_stated["update_rel_err"], errors
    if what == "bf16_parameters":
        assert "update" in broken and not check["agrees"], errors
    else:
        assert not broken and errors["aux"] == 0.0, errors


def test_reference_round_folds_the_loads_by_the_weights():
    cell = toy_cell()
    module = cell.model.build_module(cell.config)
    xs, ys = cell.model.make_data(jax.random.PRNGKey(5), cell.config, cell.traffic)
    variables = module.init(jax.random.PRNGKey(1), xs[0, 0, :1], train=False)
    aux = {"moe_stats": variables["moe_stats"]}
    weights = jnp.asarray([1.0, 3.0])
    _, _, folded = cell.model.reference_round(
        cell.config, variables["params"], aux, xs, ys, weights, 0.02
    )
    per_silo = [
        cell.model.reference_forward(cell.config, variables["params"], {}, xs[n, -1])[1]
        for n in range(2)
    ]
    want = jax.tree_util.tree_map(lambda a, b: 0.25 * a + 0.75 * b, *per_silo)
    for got, expected in zip(*map(jax.tree_util.tree_leaves, (folded, want))):
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-12)
    for layer in folded["moe_stats"].values():
        load, bias = layer["moe"]["moe_load"], layer["moe"]["balance_bias"]
        assert float(load.sum()) == pytest.approx(1.0)
        # Frozen: the fold of zeros.
        assert float(jnp.abs(bias).max()) == 0.0


def test_cell_runs_at_toy_size_and_prints_the_contract_line(tmp_path):
    cell, lines = toy_cell(), []
    result = harness.run_cell(
        cell, seed=2147484001, seconds=0.3, trace=True,
        devices=jax.devices()[:1],
        device={"platform": "cpu", "kind": "cpu", "count": jax.device_count()},
        peaks=cells.load_peaks("TPU v5 lite"), started=time.perf_counter(),
        meter=harness.CompileMeter().install(), out_dir=tmp_path,
        emit=lines.append,
    )
    window, check = map(json.loads, lines)
    assert result["correct"] is True, (window, check)
    assert window["loss_at_k"] < window["loss_first_window"]
    assert check["aux_rel_err"] <= check["tolerances"]["aux"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # No device plane on the CPU: the new readers have nothing to read.
    assert not set(NEW_READERS) & set(result["metrics"])


# --- the readers on a hand-built trace ---------------------------------------

D0, OPS, US = "/device:TPU:0", "XLA Ops", 1000
LAYER = "jit(tpfl_window)/while/body/tpfl.train/vmap()/checkpoint/layer_1/"
BACK = "jit(tpfl_window)/while/body/tpfl.train/transpose(jvp(layer_1))/"
# One device, two rounds, 0..1600 us.
EVENTS = [
    (D0, OPS, "%fusion.1", 0 * US, 40 * US),      # a latent projection
    (D0, OPS, "%fusion.2", 40 * US, 60 * US),     # the convolutions
    (D0, OPS, "%fusion.3", 100 * US, 20 * US),    # the partial rotary table
    (D0, OPS, "%block_attention_forward.1", 120 * US, 150 * US),
    (D0, OPS, "%block_attention_backward.1", 270 * US, 210 * US),
    (D0, OPS, "%fusion.4", 480 * US, 40 * US),    # the delta pass: glue
    (D0, OPS, "%fusion.5", 520 * US, 30 * US),    # residual scales: outside
    (D0, OPS, "%fusion.6", 550 * US, 50 * US),    # the router MLP
    (D0, OPS, "%sort.1", 600 * US, 30 * US),      # dispatch: the sort
    (D0, OPS, "%gmm.1", 630 * US, 250 * US),      # a grouped product
    (D0, OPS, "%fusion.7", 880 * US, 70 * US),    # the gates, backward
    (D0, OPS, "%fusion.8", 950 * US, 20 * US),    # combine
    (D0, OPS, "%fusion.9", 970 * US, 100 * US),   # the head: none of them
]
PATHS = {D0: {
    "%fusion.1": LAYER + "cca/attention/cca_proj/q_proj/dot_general",
    "%fusion.2": BACK + "cca/attention/cca_mix/mul",
    "%fusion.3": LAYER + "cca/attention/rope/mul",
    "%block_attention_forward.1": LAYER + "cca/attention/block_attention/pallas_call",
    "%block_attention_backward.1": BACK + "cca/attention/block_attention/pallas_call",
    "%fusion.4": BACK + "cca/attention/block_attention/reduce_sum",
    "%fusion.5": LAYER + "layer_1._scaled_sum/residual_scale/add",
    "%fusion.6": LAYER + "moe/moe/moe_router/dot_general",
    "%sort.1": LAYER + "moe/moe/moe_dispatch/sort",
    "%gmm.1": LAYER + "moe/moe/moe_experts/gmm",
    "%fusion.7": BACK + "moe/moe_experts/mul",
    "%fusion.8": LAYER + "moe/moe/moe_combine/reduce_sum",
    "%fusion.9": "jit(tpfl_window)/while/body/tpfl.train/head_cross_entropy/dot_general",
}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_are_silent_without_a_trace(name):
    read = cells.load_reader(name)
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None


def test_new_readers_on_the_hand_built_trace(monkeypatch):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("zaya.xplane.pb", EVENTS, PATHS),
    )
    obs = {
        "trace": {}, "trace_rounds": 2,
        "peaks": cells.load_peaks("TPU v5 lite"),
    }
    value = {name: cells.load_reader(name)(obs) for name in NEW_READERS}
    # 40 + 60 + 20 + 150 + 210 + 40 us over two rounds.
    assert value["cca_ms_per_round"] == pytest.approx(0.26)
    assert value["cca_mix_ms_per_round"] == pytest.approx(0.03)
    # Of the 400 us under both scopes, 360 are the kernels'.
    assert value["cca_attention_kernel_share_pct"] == pytest.approx(90.0)
    assert value["top1_moe_ms_per_round"] == pytest.approx(0.21)
    assert value["top1_routing_ms_per_round"] == pytest.approx(0.05)
    assert value["top1_experts_ms_per_round"] == pytest.approx(0.16)
    # The layer is its routing and its experts, nothing else.
    assert value["top1_moe_ms_per_round"] == pytest.approx(
        value["top1_routing_ms_per_round"] + value["top1_experts_ms_per_round"]
    )
    cell = cells.load_cell(CELL)
    least_ms = 1e3 * cell.model.expert_flops_per_round(
        cell.config, cell.traffic
    ) / 197e12
    assert least_ms == pytest.approx(31.39, rel=1e-3)
    assert value["top1_experts_roofline_pct"] == pytest.approx(100 * least_ms / 0.16)
    # The XLA block loop (no kernel by name) reads 0, not nothing.
    renamed = [
        (d, line, name.replace("block_attention_", "while_"), t, dur)
        for d, line, name, t, dur in EVENTS
    ]
    paths = {D0: {k.replace("block_attention_", "while_"): v for k, v in PATHS[D0].items()}}
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("loop.xplane.pb", renamed, paths),
    )
    assert cells.load_reader("cca_attention_kernel_share_pct")(obs) == 0.0
    # A program that names none of the scopes (the parent's): nothing.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("parent.xplane.pb", EVENTS, {D0: {}}),
    )
    assert all(cells.load_reader(name)(obs) is None for name in NEW_READERS)
