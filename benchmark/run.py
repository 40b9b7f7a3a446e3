#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json in this process, on this machine's chips.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU the cell asks for: with no TPU, an unknown device kind or
too few chips it raises before anything runs, exits non-zero and prints
no result (it never falls back to the CPU). Inputs and weights are made
on the device from ``--seed``. The LAST line of standard output is the
result, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``); the
lines before it are informative. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a short traced slice that follows the measured window.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark import cells, harness

    cell = cells.load_cell(args.workload)

    import jax

    from tpfl.parallel import require_chip

    # First act on the device: no TPU, or fewer chips than the cell
    # asks for, raises here, before anything is configured or built.
    device = require_chip(min_count=cell.chips)
    cache_dir = harness.arm_compile_cache()
    peaks = cells.load_peaks(device["kind"])
    meter = harness.CompileMeter().install()
    print(json.dumps({
        "info": "start", "workload": cell.name, "seed": args.seed,
        "device": device, "compile_cache": cache_dir,
    }), flush=True)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        jax.devices()[: cell.chips], device, peaks, STARTED, meter,
        cells.ROOT / ".bench_out" / cell.name,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
