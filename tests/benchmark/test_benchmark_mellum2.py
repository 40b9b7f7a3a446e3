"""The cell ``mellum2_silo_8k`` and what PR 32 added to the benchmark:
its files load and state the cut, parameter count and multiplies are the
hand counts, one federated round through the engine meets the plain
reference (the routers' loads as ``aux`` too) at toy widths on the CPU,
the cell runs end to end at toy size, and the five new readers read a
hand-built trace and stay silent on an empty one."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, harness, scope_paths
from toy_mellum2 import CELL, toy_cell

NEW_READERS = (
    "moe_ms_per_round", "moe_routing_ms_per_round", "moe_experts_ms_per_round",
    "moe_experts_roofline_pct", "window_attention_ms_per_round",
)


def test_cell_files_load_and_state_the_cut():
    cell = cells.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1
    assert traffic == {
        "nodes": 2, "local_batches": 1, "batch": 2, "seq": 8192, "window": 2,
        "driver": "pipeline", "telemetry": False, "codec": "dense",
        "mesh": None, "loss_rounds": 8,
        "check": {"nodes": 2, "local_batches": 1, "batch": 1, "seq": 2048},
    }
    # The band cuts in the check too.
    assert traffic["check"]["seq"] > cfg["sliding_window"]
    # Published widths, unchanged; depth, experts held and vocabulary
    # reduced and said so.
    published = {
        "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "moe_intermediate_size": 896, "num_experts_per_tok": 8,
        "sliding_window": 1024, "rms_norm_eps": 1e-6, "intermediate_size": 7168,
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "attention_bias": False, "hidden_act": "silu",
        "max_position_embeddings": 131072,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = next(
        c for c in cells.load_benchmark()["configs"] if c["name"] == cfg["name"]
    )
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{cfg['name']}.json"
    assert entry["source"] == cfg["source"]
    assert cfg["layers"] == [0, 1, 2, 3] and cfg["num_hidden_layers"] == 4
    assert cfg["published"] == {
        "num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304,
    }
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["experts_held"] == {
        "first": 0, "count": cfg["num_experts"], "router_width": 64,
        "experts_per_token": 8,
    }
    assert cfg["num_experts"] * 4 == cfg["published"]["num_experts"]
    assert "FOUR chips" in cfg["deployment"] and "PARTIAL" in cfg["deployment"]
    for key in ("norm_placement", "qk_norm", "rotary_pairing", "yarn", "router",
                "load_balancing_loss", "mtp_head", "intermediate_size", "data"):
        assert cfg["assumed"][key]
    workload = next(
        w for w in cells.load_benchmark()["workloads"] if w["name"] == CELL
    )
    assert "HALF" in workload["why"] and "FOUR times" in workload["why"]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(names) and "mfu_device_pct" in names
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
    assert count(params) == cfg["parameters"] == 595_153_152
    assert {k: count(v) for k, v in params.items()} == cfg["parameters_by_part"]
    layer = params["layer_3"]
    assert {
        "attention": count(layer["attention"]),
        "router": count(layer["moe"]["router"]),
        "norms": count(layer["norm_attention"]) + count(layer["norm_moe"]),
        "experts": count(layer["moe"]) - count(layer["moe"]["router"]),
        "one_expert": 3 * 2304 * 896,
    } == cfg["parameters_of_a_layer"]
    assert layer["moe"]["gate_up_proj"].shape == (16, 2304, 1792)
    assert layer["moe"]["router"].shape == (2304, 64)
    # The counter the engine carries: a load per published expert a layer.
    stats = shapes["moe_stats"]
    assert sorted(stats) == [f"layer_{i}" for i in range(4)]
    assert stats["layer_0"]["moe"]["moe_load"].shape == (64,)
    # The hand count at S = 8192: a token sends 8 x 16 / 64 = 2 rows to
    # the held experts; a banded query sees 960.0625 keys on average,
    # a full one 4096.5.
    d, f, s = 2304, 896, 8192
    band = (1024 * 1025 / 2 + (s - 1024) * 1024) / s
    per_layer = 2 * d * 4096 + 2 * d * 512 + d * 64 + 2 * 3 * d * f
    want = 4 * per_layer + 2 * 4096 * (3 * band + (s + 1) / 2) + d * 24576
    assert band == 960.0625
    assert cell.model.fwd_mults_per_sample(cfg, cell.traffic) == int(want) == 248_845_824
    # The experts' roofline count: 6 x 3 d f x (32,768 tokens x 2 rows) x 4.
    assert cell.model.expert_flops_per_round(cfg, cell.traffic) == (
        6 * 3 * d * f * 65536 * 4
    )


def test_engine_round_meets_reference_round():
    """One federated round, 2 nodes, uneven weights, the harness's own
    check (the comparison that decides ``correct`` on the chip) — of the
    share the cell holds and of another."""
    for first in (0, 8):
        check = harness.check_against_reference(
            toy_cell(first), 3, jax.devices()[:1]
        )
        assert check["agrees"] and check["nodes"] == 2
        assert check["loss_rel_err"] < 1e-5 and check["update_rel_err"] < 1e-3
        assert check["aux_rel_err"] < 1e-5
        assert len(set(check["losses_reference"])) == 2


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
        np.testing.assert_allclose(got, expected, atol=1e-6)
        assert float(got.sum()) == pytest.approx(1.0)


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
# One device, two rounds, 0..1200 us.
EVENTS = [
    (D0, OPS, "%fusion.1", 0 * US, 40 * US),     # router
    (D0, OPS, "%sort.1", 40 * US, 60 * US),      # dispatch: the sort
    (D0, OPS, "%gmm.1", 100 * US, 300 * US),     # a grouped product
    (D0, OPS, "%fusion.2", 400 * US, 100 * US),  # the gates, backward
    (D0, OPS, "%fusion.3", 500 * US, 50 * US),   # combine
    (D0, OPS, "%while.1", 600 * US, 400 * US),   # the banded block loop
    (D0, OPS, "%fusion.4", 1000 * US, 100 * US),  # the full layer's rotary
    (D0, OPS, "%fusion.5", 1100 * US, 100 * US),  # the head: none of them
]
PATHS = {D0: {
    "%fusion.1": LAYER + "moe/moe/moe_router/dot_general",
    "%sort.1": LAYER + "moe/moe/moe_dispatch/sort",
    "%gmm.1": LAYER + "moe/moe/moe_experts/gmm",
    "%fusion.2": BACK + "moe/moe_experts/mul",
    "%fusion.3": LAYER + "moe/moe/moe_combine/reduce_sum",
    "%while.1": LAYER + "window_attention/attention/block_attention/while",
    "%fusion.4": LAYER.replace("layer_1", "layer_3") + "full_attention/attention/rope/mul",
    "%fusion.5": "jit(tpfl_window)/while/body/tpfl.train/head_cross_entropy/dot_general",
}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_are_silent_without_a_trace(name):
    read = cells.load_reader(name)
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None


def test_new_readers_on_the_hand_built_trace(monkeypatch):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("mellum.xplane.pb", EVENTS, PATHS),
    )
    obs = {
        "trace": {}, "trace_rounds": 2,
        "peaks": cells.load_peaks("TPU v5 lite"),
    }
    value = {name: cells.load_reader(name)(obs) for name in NEW_READERS}
    assert value["moe_ms_per_round"] == pytest.approx(0.275)
    assert value["moe_routing_ms_per_round"] == pytest.approx(0.075)
    assert value["moe_experts_ms_per_round"] == pytest.approx(0.2)
    # The layer is its routing and its experts, nothing else.
    assert value["moe_ms_per_round"] == pytest.approx(
        value["moe_routing_ms_per_round"] + value["moe_experts_ms_per_round"]
    )
    assert value["window_attention_ms_per_round"] == pytest.approx(0.2)
    cell = cells.load_cell(CELL)
    least_ms = 1e3 * cell.model.expert_flops_per_round(
        cell.config, cell.traffic
    ) / 197e12
    assert least_ms == pytest.approx(49.45, rel=1e-3)
    assert value["moe_experts_roofline_pct"] == pytest.approx(100 * least_ms / 0.2)
    # A program that names none of the scopes (the parent's): nothing.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("parent.xplane.pb", EVENTS, {D0: {}}),
    )
    assert all(cells.load_reader(name)(obs) is None for name in NEW_READERS)
