"""Every file the benchmark's names point at: ``BENCHMARK.json`` against
its contract, each cell's configuration, traffic, model file and
per-layer readers, the peaks table, and the arithmetic kept with the
configurations."""

import json
import re
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells
from toy import toy_cell

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert BENCH["command"][-1].startswith("benchmark/")
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    # A full check of the full 24 cells must fit the driver's 43200 s.
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines_are_in_the_allowed_characters():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert len(set(WORKLOADS)) == len(WORKLOADS)
    for name in names + WORKLOADS + CONFIGS:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
        for cell in m.get("workloads", []):
            assert cell in WORKLOADS, m
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_end_to_end_metrics_carry_one_bound_each_and_layers_none():
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace"), m
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["moves"] in e2e, m
        assert 1 <= len(m["layer"]) <= 200


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(WORKLOADS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in BENCH["workloads"]} == set(CONFIGS)


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_loads_with_every_file_it_names(name):
    cell = cells.load_cell(name)
    assert cell.name == name and cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "rounds_per_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    for metric in cell.per_layer:
        assert callable(cells.load_reader(metric["name"]))
        # A per-layer metric is reported only where the one it moves is.
        assert metric["moves"] in {m["name"] for m in cell.end_to_end}
    if cell.chips == 4:
        assert cell.traffic["mesh"] == {"nodes": 4}
    assert cell.traffic["nodes"] % (cell.chips * 2 if cell.chips > 1 else 1) == 0
    assert cell.traffic["check"]["nodes"] >= 2


@pytest.mark.parametrize("config", BENCH["configs"], ids=CONFIGS)
def test_configuration_file_says_what_benchmark_json_says(config):
    with open(cells.ROOT / config["file"]) as f:
        on_file = json.load(f)
    assert on_file["name"] == config["name"]
    assert on_file["reduced"] == config["reduced"] == []
    assert config["file"].startswith("benchmark/configs/")
    assert "assumed" in on_file and "deployment" in on_file


def _copy_benchmark(tmp_path, edit):
    """A root holding BENCHMARK.json (edited) and the config files."""
    bench = json.loads(json.dumps(BENCH))
    edit(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(
        cells.HERE / "configs", tmp_path / "benchmark" / "configs"
    )
    return tmp_path


def test_unknown_key_in_a_cell_is_refused(tmp_path):
    root = _copy_benchmark(
        tmp_path, lambda b: b["workloads"][0].update(rate=3)
    )
    with pytest.raises(cells.BenchmarkFileError, match="unknown keys.*rate"):
        cells.load_cell(WORKLOADS[0], root=root)


def test_unknown_workload_and_configuration_are_refused(tmp_path):
    with pytest.raises(cells.BenchmarkFileError, match="not in BENCHMARK.json"):
        cells.load_cell("no_such_cell")
    root = _copy_benchmark(
        tmp_path, lambda b: b["workloads"][0].update(config="nope")
    )
    with pytest.raises(cells.BenchmarkFileError, match="configuration 'nope'"):
        cells.load_cell(WORKLOADS[0], root=root)


def test_traffic_keys_are_checked():
    with pytest.raises(cells.BenchmarkFileError, match="unknown keys.*burst"):
        cells._check_keys(
            "t", {"burst": 1}, cells.TRAFFIC_KEYS, set()
        )
    with pytest.raises(cells.BenchmarkFileError, match="missing keys.*window"):
        cells._check_keys(
            "t", {"nodes": 1}, cells.TRAFFIC_KEYS, {"nodes", "window"}
        )
    with pytest.raises(cells.BenchmarkFileError, match="missing file"):
        cells.load_traffic("no_such_mix")
    with pytest.raises(cells.BenchmarkFileError, match="missing file"):
        cells.load_reader("no_such_metric")


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    peaks = cells.load_peaks("TPU v5 lite")
    assert peaks == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9,
    }
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(cells.BenchmarkFileError, match="peaks.json"):
            cells.load_peaks(kind)


@pytest.mark.parametrize(
    "name, mults",
    [
        # ResNet-18 on 32x32: 555 M multiplies (He et al.'s 1.8 G is at
        # 224x224 with the 7x7 stem).
        ("resnet18_sync_long", 555_468_800),
        # 12 x (4 d^2 + 8 d^2 + S d) + d V at d=768, S=1024, V=50257.
        ("gpt2s_silo_1chip", 12 * (12 * 768**2 + 1024 * 768) + 768 * 50257),
    ],
)
def test_forward_multiplies_at_the_published_sizes(name, mults):
    cell = cells.load_cell(name)
    assert cell.model.fwd_mults_per_sample(cell.config, cell.traffic) == mults


@pytest.mark.parametrize("name", ["resnet18_sync_long", "gpt2s_silo_1chip"])
def test_parameter_count_is_the_one_the_configuration_states(name):
    cell = cells.load_cell(name)
    module = cell.model.build_module(cell.config)
    shape = cell.model.input_shape(cell.config, cell.traffic)
    dummy = jnp.zeros((1, *shape), getattr(module, "input_dtype", jnp.float32))
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), dummy, train=False)
    )
    count = sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(variables["params"])
    )
    assert count == cell.config["parameters"]


@pytest.mark.parametrize("name", ["resnet18_observed_short", "gpt2s_silo_4chip"])
def test_data_is_a_function_of_the_seed(name):
    cell = toy_cell(name)
    make = jax.jit(
        lambda key: cell.model.make_data(key, cell.config, cell.traffic)
    )
    xs, ys = make(jax.random.PRNGKey(3))
    xs2, ys2 = make(jax.random.PRNGKey(3))
    xs3, _ = make(jax.random.PRNGKey(4))
    t = cell.traffic
    assert xs.shape[:3] == ys.shape[:3] == (t["nodes"], t["local_batches"], t["batch"])
    assert (xs == xs2).all() and (ys == ys2).all()
    assert not (xs == xs3).all()
    assert ys.dtype == jnp.int32
    if "seq" in t:
        # Next-token targets: ys is xs shifted by one.
        assert (xs[..., 1:] == ys[..., :-1]).all()
        assert int(xs.max()) < cell.config["vocab_size"]
    else:
        assert int(ys.max()) < cell.config["num_classes"]
