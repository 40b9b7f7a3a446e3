"""The cell ``phi4flash_silo_8k`` and what PR 27 added to the benchmark:
its files load and say what the contract asks, the plain reference meets
the zoo module (forward, loss, gradients, one federated round through the
engine) at toy widths on the CPU, the new readers read a hand-built trace
and stay silent on an empty one, and the scan's byte count is the hand
count."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells, harness, scope_paths
from toy_phi4flash import CELL, toy_cell

NEW_READERS = (
    "ssm_scan_ms_per_round", "ssm_scan_roofline_pct",
    "attention_ms_per_round", "mixer_other_ms_per_round",
)


def test_cell_files_load_and_state_the_cut():
    cell = cells.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1
    assert traffic == {
        "nodes": 2, "local_batches": 1, "batch": 1, "seq": 8192, "window": 2,
        "driver": "pipeline", "telemetry": False, "codec": "dense",
        "mesh": None, "loss_rounds": 8,
        "check": {"nodes": 2, "local_batches": 1, "batch": 1, "seq": 1024},
    }
    # Published widths, unchanged; depth and vocabulary reduced and said so.
    published = {
        "hidden_size": 2560, "intermediate_size": 10240,
        "num_attention_heads": 40, "num_key_value_heads": 20,
        "sliding_window": 512, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
        "hidden_act": "silu",
    }
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    entry = next(
        c for c in cells.load_benchmark()["configs"] if c["name"] == cfg["name"]
    )
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{cfg['name']}.json"
    assert cfg["layers"] == [16, 17, 18, 19] and cfg["num_hidden_layers"] == 4
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] + 0
    assert cfg["published"]["num_hidden_layers"] == 32
    assert "eight chips" in cfg["deployment"]
    assert "sliding-window" in cfg["not_in_the_cut"]
    for key in ("mamba_sizes", "differential_attention", "head_pairing",
                "attention_biases", "positions", "data"):
        assert cfg["assumed"][key]
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(names) and "mfu_device_pct" in names
    assert {m["name"] for m in cell.end_to_end} == {
        "rounds_per_s", "peak_hbm_gb", "loss_at_k", "setup_s",
    }


def test_configuration_counts_its_parameters_and_multiplies():
    cell = cells.load_cell(CELL)
    module = cell.model.build_module(cell.config)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
    )
    assert count(shapes) == cell.config["parameters"]
    assert {k: count(v) for k, v in shapes.items()} == (
        cell.config["parameters_by_part"]
    )
    # ISSUE 27's desk count: 542 M multiplies a token at S = 8192.
    per_token = cell.model.fwd_mults_per_sample(cell.config, cell.traffic)
    assert per_token == pytest.approx(542e6, rel=2e-3)


def test_scan_min_bytes_is_the_hand_count():
    model = cells.load_cell(CELL).model
    cfg = {
        "hidden_size": 4, "mamba": {"expand": 2, "d_state": 3},
        "compute_dtype": "bfloat16", "mb_per_layer": 2,
        "published": {"num_hidden_layers": 8}, "layers": [4, 5, 6],
    }
    traffic = {"nodes": 2, "local_batches": 1, "batch": 3, "seq": 10}
    # One Mamba layer (4), D = 8, N = 3, S = 10, bf16 c / B / C / s.
    c = s = g = 10 * 8 * 2
    delta = 10 * 8 * 4
    b_and_c = 2 * 10 * 3 * 2
    a_and_d = (8 * 3 + 8) * 4
    inputs = c + delta + b_and_c + a_and_d
    forward = inputs + s
    backward = inputs + g + inputs  # read again + dy, write six gradients
    assert model.scan_min_bytes_per_round(cfg, traffic) == 6 * (forward + backward)


def _setup(full_pattern):
    cell = toy_cell(full_pattern)
    module = cell.model.build_module(cell.config)
    xs, ys = cell.model.make_data(jax.random.PRNGKey(7), cell.config, cell.traffic)
    x, y = xs[0, 0], ys[0, 0]
    params = module.init(jax.random.PRNGKey(1), x[:1], train=False)["params"]
    # Away from the initial values a wrong reading could hide behind:
    # zero biases, unit scales, lambda at lambda_init.
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 1000))
    params = jax.tree_util.tree_map(
        lambda v: v + 0.05 * jax.random.normal(next(keys), v.shape), params
    )
    return cell, module, params, x, y


def _max_rel(a, b):
    """Largest per-leaf error relative to the leaf's largest entry. The
    key projection's bias is left out: it has NO gradient (a shift of
    every key moves a query's scores together), so both sides hold
    rounding there, and it is held to that instead."""
    flat_a, flat_b = (
        jax.tree_util.tree_flatten_with_path(t)[0] for t in (a, b)
    )
    assert len(flat_a) == len(flat_b)
    worst = 0.0
    for (path, u), (_, v) in zip(flat_a, flat_b):
        if "k_proj" in jax.tree_util.keystr(path) and u.ndim == 1:
            assert float(jnp.abs(u).max()) < 1e-6 > float(jnp.abs(v).max())
            continue
        worst = max(worst, float(jnp.abs(u - v).max() / jnp.abs(v).max()))
    return worst


@pytest.mark.parametrize("full_pattern", [True, False], ids=["n8_all", "stage_4_7"])
def test_reference_meets_the_module(full_pattern):
    """Forward, loss (both of the module's paths) and gradients."""
    cell, module, params, x, y = _setup(full_pattern)

    def zoo(p):
        out = module.apply({"params": p}, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(out, y).mean(), out

    def owned(p):
        return module.apply({"params": p}, x, train=True, targets=y), None

    def ref(p):
        out, _ = cell.model.reference_forward(cell.config, p, {}, x)
        return cell.model._loss(cell.config, p, x, y), out

    grad = lambda f: jax.jit(jax.value_and_grad(f, has_aux=True))(params)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        (l_ref, ref_logits), g_ref = grad(ref)
        (l_own, _), g_own = grad(owned)
        if full_pattern:  # the logits path: forward only (compile time)
            l_zoo, logits = jax.jit(zoo)(params)
        else:
            (l_zoo, logits), g_zoo = grad(zoo)
            assert _max_rel(g_zoo, g_ref) < 2e-4
    assert _max_rel(logits, ref_logits) < 2e-5
    assert abs(l_zoo - l_ref) / l_ref < 1e-5 and abs(l_own - l_ref) / l_ref < 1e-5
    assert _max_rel(g_own, g_ref) < 2e-4


def test_engine_round_meets_reference_round():
    """One federated round, 2 nodes, uneven weights, the harness's own
    check (the comparison that decides ``correct`` on the chip)."""
    check = harness.check_against_reference(toy_cell(), 3, jax.devices()[:1])
    assert check["agrees"] and check["nodes"] == 2
    assert check["loss_rel_err"] < 1e-5 and check["update_rel_err"] < 1e-3
    assert len(set(check["losses_reference"])) == 2


def test_cell_runs_at_toy_size_and_prints_the_contract_line(tmp_path):
    cell, lines = toy_cell(), []
    result = harness.run_cell(
        cell, seed=0, seconds=0.3, trace=True, devices=jax.devices()[:1],
        device={"platform": "cpu", "kind": "cpu", "count": jax.device_count()},
        peaks=cells.load_peaks("TPU v5 lite"), started=time.perf_counter(),
        meter=harness.CompileMeter().install(), out_dir=tmp_path,
        emit=lines.append,
    )
    window, check = map(json.loads, lines)
    assert result["correct"] is True, (window, check)
    assert window["loss_at_k"] < window["loss_first_window"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # No device plane on the CPU: the new readers have nothing to read.
    assert not set(NEW_READERS) & set(result["metrics"])


# --- the readers on a hand-built trace ---------------------------------------

D0, OPS, US = "/device:TPU:0", "XLA Ops", 1000
WINDOW = "jit(tpfl_window)/while/body/tpfl.train/vmap()/checkpoint/layer_16/"
# One device, two rounds, 0..1000 us. Self times: while.1 100 - 60 = 40.
EVENTS = [
    (D0, OPS, "%while.1", 0 * US, 100 * US),    # the scan's chunk loop
    (D0, OPS, "%fusion.1", 10 * US, 60 * US),   # a step inside it
    (D0, OPS, "%fusion.2", 100 * US, 200 * US),  # Mamba projections
    (D0, OPS, "%fusion.3", 300 * US, 300 * US),  # attention, backward
    (D0, OPS, "%fusion.4", 600 * US, 80 * US),  # the gated memory unit
    (D0, OPS, "%fusion.5", 680 * US, 320 * US),  # an MLP: none of the four
]
PATHS = {D0: {
    "%while.1": WINDOW + "mamba/mixer/ssm_scan/while",
    "%fusion.1": WINDOW + "mamba/mixer/ssm_scan/while/body/mul",
    "%fusion.2": WINDOW + "mamba/mixer/in_proj/dot_general",
    "%fusion.3": "jit(tpfl_window)/tpfl.train/transpose(jvp(diff_attention))/while",
    "%fusion.4": WINDOW.replace("16", "18") + "gmu/mixer/mul",
    "%fusion.5": WINDOW + "mlp/mlp/down_proj/dot_general",
}}


def test_scope_paths_sums_self_time_by_whole_names():
    table = scope_paths.self_ms_by_scope(
        scope_paths.busiest_device_rows(EVENTS), PATHS,
        ("ssm_scan", "mamba", "diff_attention", "gmu", "mlp", "scan"),
    )
    assert table == pytest.approx({
        "ssm_scan": 0.1, "mamba": 0.3, "diff_attention": 0.3, "gmu": 0.08,
        "mlp": 0.32, "scan": 0.0,  # "scan" is no whole name of any path
    })
    assert scope_paths.carries("a/transpose(jvp(mlp))/dot", "mlp")
    assert not scope_paths.carries("a/mlp_dim/dot", "mlp")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_are_silent_without_a_trace(name):
    read = cells.load_reader(name)
    assert read({"trace": None, "trace_rounds": 0}) is None
    assert read({}) is None


def test_new_readers_on_the_hand_built_trace(monkeypatch):
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("trace.xplane.pb", EVENTS, PATHS),
    )
    obs = {
        "trace": {}, "trace_rounds": 2,
        "peaks": cells.load_peaks("TPU v5 lite"),
    }
    value = {name: cells.load_reader(name)(obs) for name in NEW_READERS}
    assert value["ssm_scan_ms_per_round"] == pytest.approx(0.05)
    assert value["attention_ms_per_round"] == pytest.approx(0.15)
    # (mamba 0.3 - scan 0.1 + gmu 0.08) / 2 rounds
    assert value["mixer_other_ms_per_round"] == pytest.approx(0.14)
    cell = cells.load_cell(CELL)
    least_ms = 1e3 * cell.model.scan_min_bytes_per_round(
        cell.config, cell.traffic
    ) / 819e9
    assert value["ssm_scan_roofline_pct"] == pytest.approx(100 * least_ms / 0.05)
    # A program that names none of the scopes (the parent's): nothing.
    monkeypatch.setattr(
        scope_paths.program_trace, "find_trace",
        lambda obs: ("trace.xplane.pb", EVENTS, {D0: {}}),
    )
    assert all(cells.load_reader(name)(obs) is None for name in NEW_READERS)
