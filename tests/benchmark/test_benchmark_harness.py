"""The harness walked through on the virtual CPU devices at toy sizes:
every cell's own files and driver, the last line's keys, the refusal to
run without a TPU, and the check against the plain reference. No number
from here is a device number: the device is the CPU and says so."""

import json
import math
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from benchmark import cells, harness
from toy import toy_cell

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, trace, tmp_path, seconds=0.5):
    lines = []
    result = harness.run_cell(
        cell, seed=0, seconds=seconds, trace=trace,
        devices=jax.devices()[: cell.chips],
        device={"platform": "cpu", "kind": "cpu", "count": jax.device_count()},
        peaks=cells.load_peaks("TPU v5 lite"), started=time.perf_counter(),
        meter=METER, out_dir=tmp_path, emit=lines.append,
    )
    return result, [json.loads(line) for line in lines]


METER = harness.CompileMeter().install()


@pytest.mark.parametrize(
    "name, trace",
    [
        ("resnet18_sync_long", False),
        ("resnet18_observed_short", True),
        ("gpt2s_silo_1chip", True),
        ("gpt2s_silo_4chip", False),
    ],
)
def test_cell_runs_at_toy_size_and_prints_the_contract_line(name, trace, tmp_path):
    cell = toy_cell(name)
    result, info = _run(cell, trace, tmp_path)
    # The line is JSON with exactly the contract's keys (the CPU has no
    # device plane, so a traced run carries no breakdown here).
    assert set(json.loads(json.dumps(result))) == RESULT_KEYS
    assert result["correct"] is True, info
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    listed = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in listed}
    assert set(result["metrics"]) <= set(units)
    for metric, entry in result["metrics"].items():
        assert set(entry) == {"value", "unit"} and entry["unit"] == units[metric]
        assert math.isfinite(entry["value"])
    window, check = info
    assert window["info"] == "window" and check["info"] == "reference_check"
    assert window["compile"]["in_window"] == 0
    assert window["loss_at_k"] < window["loss_first_window"]
    assert check["agrees"] and check["nodes"] == cell.traffic["check"]["nodes"]
    # float32 against float32: far inside the chip's bf16 tolerances.
    assert check["loss_rel_err"] < 1e-4 and check["update_rel_err"] < 1e-3
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert "device_idle_pct" not in result["metrics"]  # no device trace
        assert (tmp_path / "trace" / "inventory.json").is_file()
        if cell.traffic["driver"] == "sequential":
            assert result["metrics"]["host_ms_per_window"]["value"] > 0
            # Timed window by window: the rate at the median window.
            assert window["rounds_per_s"] == pytest.approx(
                cell.traffic["window"] * 1e3 / window["window_ms_median"]
            )
    else:
        assert set(result["metrics"]) == set(units)
        assert result["metrics"]["setup_s"]["value"] > 0
        rate = result["metrics"]["rounds_per_s"]["value"]
        assert window["rounds"] == result["attempted"] * cell.traffic["window"]
        assert window["rounds_per_s_elapsed"] == pytest.approx(
            window["rounds"] / window["seconds"]
        )
        if cell.traffic["driver"] == "pipeline":
            # Free-running: rounds completed over seconds elapsed.
            assert rate == window["rounds_per_s_elapsed"]


def test_a_reference_that_disagrees_makes_the_run_incorrect(tmp_path, monkeypatch):
    """The comparison that decides ``correct`` has teeth: a reference
    stepping 1.2x as far puts ~17% on the update, beyond the LM's 3%."""
    cell = toy_cell("gpt2s_silo_1chip")
    honest = cell.model.reference_round
    monkeypatch.setattr(
        cell.model, "reference_round",
        lambda cfg, p, a, xs, ys, w, lr: honest(cfg, p, a, xs, ys, w, 1.2 * lr),
    )
    check = harness.check_against_reference(cell, 0, jax.devices()[:1])
    assert not check["agrees"]
    assert check["update_rel_err"] > cell.model.CHECK_TOLERANCES["update"]
    assert check["update_worst_leaves"][0][2] > 0  # names where the error is
    assert check["loss_rel_err"] < 1e-4  # round-start losses are the same


def test_uneven_weights_reach_the_fold(monkeypatch):
    """The check's weights are 1..n: a reference folding uniformly must
    disagree with the engine."""
    cell = toy_cell("resnet18_sync_long")
    honest = cell.model.reference_round
    monkeypatch.setattr(
        cell.model, "reference_round",
        lambda cfg, p, a, xs, ys, w, lr: honest(cfg, p, a, xs, ys, w * 0 + 1, lr),
    )
    assert not harness.check_against_reference(cell, 0, jax.devices()[:1])["agrees"]


@pytest.mark.parametrize("name", ["resnet18_sync_long", "gpt2s_silo_4chip"])
def test_one_jit_init_equals_the_engines_own(name):
    cell = toy_cell(name)
    fed = harness.build_federation(
        cell, cell.traffic, 5, jax.devices()[: cell.chips]
    )
    shape = cell.model.input_shape(cell.config, cell.traffic)
    params, aux = fed.engine.init_state(shape)
    for got, want in zip(
        jax.tree_util.tree_leaves((fed.params, fed.aux or {})),
        jax.tree_util.tree_leaves((params, aux)),
    ):
        # One fused program against op-by-op: equal to the last bit or two.
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)
        assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
    assert fed.xs.shape[0] == fed.engine.padded_nodes == cell.traffic["nodes"]


def test_percentile_is_nearest_rank():
    values = [float(v) for v in range(1, 101)]
    assert harness.percentile(values, 0.9) == 90.0
    assert harness.percentile(values, 0.5) == 50.0
    assert harness.percentile([3.0], 0.9) == 3.0
    assert harness.percentile([1.0, 2.0], 0.9) == 2.0


def test_spans_record_and_sum_by_name():
    spans = harness.Spans()
    with spans("dispatch"):
        pass
    with spans("finalize"):
        pass
    with spans("dispatch"):
        pass
    assert [name for name, _, _ in spans.records] == ["dispatch", "finalize", "dispatch"]
    assert len(spans.durations("dispatch")) == 2
    assert all(d >= 0 for d in spans.durations("dispatch"))


TRACE = {
    "busy_s": 0.9, "busy_s_max": 1.0, "window_s": 1.2,
    "collective_s": 0.2, "collective_exposed_s": 0.05,
}


def _obs(**over):
    spans = harness.Spans()
    spans.records = [
        ("dispatch", 0.0, 0.001), ("finalize", 0.0, 0.003),
        ("dispatch", 0.0, 0.002), ("finalize", 0.0, 0.004),
        ("dispatch", 0.0, 0.001), ("finalize", 0.0, 0.001),
    ]
    obs = {
        "spans": spans, "trace": TRACE, "trace_rounds": 10,
        "compiles_in_window": 0, "flops_per_round": 1.97e13, "chips": 1,
        "peaks": cells.load_peaks("TPU v5 lite"),
        "memory": {"peak_bytes_in_use": 2_500_000_000},
    }
    obs.update(over)
    return obs


@pytest.mark.parametrize(
    "metric, expected",
    [
        ("host_ms_per_window", 4.0),  # sums 4, 6, 2 ms: the median
        ("compiles_in_window", 0),
        ("device_ms_per_round", 100.0),
        ("mfu_device_pct", 100.0),  # 1.97e13 FLOPs in 0.1 s at 197 TFLOP/s
        ("collective_ms_per_round", 20.0),
        ("collective_exposed_pct", 25.0),
        ("device_idle_pct", 25.0),
        ("hbm_in_use_peak_gb", 2.5),
    ],
)
def test_layer_metric_reader(metric, expected):
    assert cells.load_reader(metric)(_obs()) == pytest.approx(expected)


@pytest.mark.parametrize(
    "metric",
    [m["name"] for m in cells.load_benchmark()["per_layer"]
     if m["source"] == "device_trace"],
)
def test_reader_with_nothing_to_read_returns_nothing(metric):
    assert cells.load_reader(metric)(_obs(trace=None)) is None


def test_readers_without_their_spans_or_counters_return_nothing():
    assert cells.load_reader("host_ms_per_window")(_obs(spans=harness.Spans())) is None
    no_collective = dict(TRACE, collective_s=0.0, collective_exposed_s=0.0)
    assert cells.load_reader("collective_ms_per_round")(_obs(trace=no_collective)) is None
    assert cells.load_reader("collective_exposed_pct")(_obs(trace=no_collective)) is None
    assert cells.load_reader("hbm_in_use_peak_gb")(
        _obs(memory={"peak_bytes_in_use": 0})
    ) is None


def test_the_command_refuses_to_run_without_a_tpu():
    """``python3 benchmark/run.py`` on this CPU: non-zero exit, no result
    line, and the reason names the missing TPU (never a CPU fallback)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet18_sync_long",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no TPU" in proc.stderr
