"""The ``setup_*`` readers (PR 36) on a toy cell, on the CPU: they read
the program's own set-up account after the traced slice, so every one
is finite, the four phases sum to no more than the run's ``setup_s``,
and a program with no account (the parent) or a window that compiled
reads nothing. Seconds from here are the CPU's, not device numbers."""

import json
import math
import time

import jax
import pytest

from benchmark import cells, harness, setup_account
from toy import toy_cell
from tpfl.management import profiling

PHASE_METRICS = {
    "trace": "setup_trace_s", "lower": "setup_lower_s",
    "load": "setup_load_s", "compile": "setup_compile_s",
}
NEW = [
    *PHASE_METRICS.values(), "setup_cache_misses", "setup_nested_traces",
    "setup_window_program_s", "setup_first_call_s", "setup_before_engine_s",
]
METER = harness.CompileMeter().install()


def test_every_new_entry_is_of_the_entry_layer_and_has_its_reader():
    entries = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    for name in NEW:
        assert entries[name] == {
            "name": name, "unit": entries[name]["unit"], "better": "lower",
            "source": entries[name]["source"], "layer": "entry",
            "moves": "setup_s",
        }, "no workloads list: every cell reports setup_s"
        assert entries[name]["unit"] == ("s" if name.endswith("_s") else "count")
        assert callable(cells.load_reader(name))
    # Appended: nothing the benchmark had moved.
    assert list(entries)[-len(NEW):] == NEW


def test_readers_on_the_toy_cell_are_finite_and_sum_inside_setup_s(tmp_path):
    cell = toy_cell("resnet18_observed_short")
    profiling.observatory.open_setup_account()
    before = profiling.observatory.setup_account()
    lines = []
    result = harness.run_cell(
        cell, seed=0, seconds=0.5, trace=True,
        devices=jax.devices()[: cell.chips],
        device={"platform": "cpu", "kind": "cpu", "count": jax.device_count()},
        peaks=cells.load_peaks("TPU v5 lite"), started=time.perf_counter(),
        meter=METER, out_dir=tmp_path, emit=lines.append,
    )
    window = json.loads(lines[0])
    assert window["compile"]["in_window"] == 0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert math.isfinite(values[name]) and values[name] >= 0, name
    # This worker's earlier tests fed the same account: this run's share
    # is what the phases grew by. No second is counted twice, so the
    # four lie inside the run's set-up; the harness's own sum of every
    # event (nested ones too) is the upper bound the issue names.
    grew = sum(
        values[metric] - before["phases"][phase]["seconds"]
        for phase, metric in PHASE_METRICS.items()
    )
    assert 0 < grew <= window["setup_s"]
    assert values["setup_window_program_s"] > 0
    assert values["setup_first_call_s"] > 0
    assert values["setup_nested_traces"] > before["phases"]["trace"]["nested_events"]


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_an_account_or_after_a_compile(
    name, monkeypatch
):
    read = cells.load_reader(name)
    assert read({"compiles_in_window": 1}) is None
    # The parent of PR 36: an observatory that keeps no account.
    monkeypatch.delattr(profiling.CompileObservatory, "setup_account")
    assert setup_account.account({"compiles_in_window": 0}) is None
    assert read({"compiles_in_window": 0}) is None
