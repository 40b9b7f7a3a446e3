"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is ``{name, config, traffic, chips, why}``. Everything that
belongs to one configuration, one traffic mix or one per-layer metric
is a file of its own, so a later PR adds cells and metrics by adding
files and entries, never by editing a file that is there:

- ``benchmark/configs/<config>.json`` — the sizes as they are run;
- ``benchmark/models/<config>.py`` — module builder, data generator,
  operation count and the plain reference of that configuration;
- ``benchmark/traffic/<traffic>.json`` — the federation's parameters;
- ``benchmark/layer_metrics/<metric>.py`` — one reader, ``read(obs)``.

An unknown key anywhere is an error, not something to ignore: a typo
in a traffic file must not silently run the default.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent

CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
TRAFFIC_KEYS = {
    "nodes", "local_batches", "batch", "seq", "window", "driver",
    "telemetry", "codec", "mesh", "loss_rounds", "check",
}
TRAFFIC_REQUIRED = TRAFFIC_KEYS - {"seq"}
CHECK_KEYS = {"nodes", "local_batches", "batch", "seq"}
DRIVERS = ("pipeline", "sequential")
MODEL_API = (
    "build_module", "input_shape", "samples_per_round", "make_data",
    "fwd_mults_per_sample", "reference_round", "SAMPLE_UNIT",
    "CHECK_TOLERANCES",
)


class BenchmarkFileError(ValueError):
    """A file of the benchmark does not say what the harness can run."""


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    model: ModuleType
    end_to_end: tuple  # the metric entries this cell reports
    per_layer: tuple


def _load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchmarkFileError(f"missing file: {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def load_peaks(device_kind: str) -> dict:
    """This device kind's row of ``peaks.json``. A kind that is not in
    the table is an error: utilisation against a guessed peak is a
    guess."""
    table = _load_json(HERE / "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise BenchmarkFileError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(k for k in table if k != 'source')})"
        )
    return table[device_kind]


def _check_keys(what: str, got: dict, allowed: set, required: set) -> None:
    unknown, missing = set(got) - allowed, required - set(got)
    if unknown or missing:
        raise BenchmarkFileError(
            f"{what}: unknown keys {sorted(unknown)}, "
            f"missing keys {sorted(missing)}"
        )


def load_traffic(name: str) -> dict:
    traffic = _load_json(HERE / "traffic" / f"{name}.json")
    _check_keys(f"traffic/{name}.json", traffic, TRAFFIC_KEYS, TRAFFIC_REQUIRED)
    _check_keys(
        f"traffic/{name}.json check", traffic["check"], CHECK_KEYS,
        CHECK_KEYS - {"seq"},
    )
    if traffic["driver"] not in DRIVERS:
        raise BenchmarkFileError(
            f"traffic/{name}.json: driver {traffic['driver']!r} not in {DRIVERS}"
        )
    if traffic["loss_rounds"] % traffic["window"] or (
        traffic["loss_rounds"] < 2 * traffic["window"]
    ):
        raise BenchmarkFileError(
            f"traffic/{name}.json: loss_rounds must be a multiple of window "
            "and at least two windows (the first window's loss is what "
            "loss_at_k must fall below)"
        )
    return traffic


def _load_python(path: Path, tag: str) -> ModuleType:
    """Import a file of the benchmark by path (its name is data: it comes
    from ``BENCHMARK.json``)."""
    if not path.is_file():
        raise BenchmarkFileError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{tag}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_model(config: str) -> ModuleType:
    path = HERE / "models" / f"{config}.py"
    module = _load_python(path, f"model_{config}")
    missing = [name for name in MODEL_API if not hasattr(module, name)]
    if missing:
        raise BenchmarkFileError(f"{path} lacks {missing}")
    return module


def load_reader(metric: str) -> Callable[[dict], Optional[float]]:
    """``read`` of ``layer_metrics/<metric>.py``: observations of one
    run -> the metric's value, or None where there is nothing to read
    (the harness then leaves the metric out of the line)."""
    path = HERE / "layer_metrics" / f"{metric}.py"
    return _load_python(path, f"metric_{metric}").read


def _metrics_of(entries: list, cell: str) -> tuple:
    return tuple(
        m for m in entries if cell in m.get("workloads", [cell])
    )


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise BenchmarkFileError(
            f"workload {name!r} is not in BENCHMARK.json "
            f"({[w['name'] for w in bench['workloads']]})"
        )
    entry = entries[0]
    _check_keys(f"workload {name}", entry, CELL_KEYS, CELL_KEYS)
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise BenchmarkFileError(
            f"workload {name}: configuration {entry['config']!r} is not "
            f"in BENCHMARK.json ({sorted(configs)})"
        )
    config = _load_json(root / configs[entry["config"]]["file"])
    if config.get("name") != entry["config"]:
        raise BenchmarkFileError(
            f"{configs[entry['config']]['file']} names itself "
            f"{config.get('name')!r}, not {entry['config']!r}"
        )
    if entry["chips"] not in (1, 4):
        raise BenchmarkFileError(f"workload {name}: chips must be 1 or 4")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=load_traffic(entry["traffic"]),
        model=load_model(entry["config"]),
        end_to_end=_metrics_of(bench["end_to_end"], name),
        per_layer=_metrics_of(bench["per_layer"], name),
    )
