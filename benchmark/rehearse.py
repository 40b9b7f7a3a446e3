#!/usr/bin/env python3
"""Compile every cell's window program for a DESCRIBED v5e, at the real
size, without a chip, and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [workload ...] [--nodes N]

Run it before a chip call, after the toy-size walk-through on the CPU
(``tests/benchmark``): a program the chip's compiler refuses — too
large for 16 GB, a sharding it cannot partition — then costs no chip
time. ``--nodes`` overrides the traffic file's count, to find the
largest federation that fits. Nothing executes: this says nothing
about results or times and is never reported as a chip run. The engine
program is built as ``dispatch_window`` fetches it
(``FederationEngine._build_program``, donating) and lowered on shapes,
since a described device holds no arrays.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

TOPOLOGY = "v5e:2x2"


def window_program(cell, devices: list, nodes: "int | None" = None) -> tuple:
    """(the cell's jitted donating window program, abstract arguments
    placed as the engine places them on ``devices``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tpfl.parallel import FederationEngine, create_mesh
    from tpfl.parallel.mesh import federation_sharding, mesh_axis_size
    from tpfl.settings import Settings

    traffic = dict(cell.traffic, nodes=nodes or cell.traffic["nodes"])
    Settings.ENGINE_TELEMETRY = bool(traffic["telemetry"])
    Settings.ENGINE_WIRE_CODEC = traffic["codec"]
    mesh = None
    if traffic["mesh"]:
        mesh = create_mesh(dict(traffic["mesh"]), devices=devices[: cell.chips])
    engine = FederationEngine(
        cell.model.build_module(cell.config), traffic["nodes"], mesh=mesh,
        learning_rate=float(cell.config["learning_rate"]),
    )
    shape = cell.model.input_shape(cell.config, traffic)
    dummy = jnp.zeros((1, *shape), getattr(engine.module, "input_dtype", jnp.float32))
    variables = jax.eval_shape(
        lambda: engine.module.init(jax.random.PRNGKey(0), dummy, train=False)
    )
    n = engine.padded_nodes
    node_sh = SingleDeviceSharding(devices[0]) if mesh is None else federation_sharding(mesh)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct((n, *shape), dtype, sharding=node_sh)

    def stacked(tree):
        return jax.tree_util.tree_map(lambda x: placed(x.shape, x.dtype), tree)

    aux = {k: v for k, v in variables.items() if k != "params"}
    xs, ys = jax.eval_shape(
        lambda: cell.model.make_data(jax.random.PRNGKey(0), cell.config, traffic)
    )
    vec = placed((), jnp.float32)
    # FedAvg: no control variates, so nothing rides replicated.
    args = (
        stacked(variables["params"]), {}, {}, stacked(aux),
        placed(xs.shape[1:], xs.dtype), placed(ys.shape[1:], ys.dtype),
        vec, vec,
    )
    telemetry, codec, frac = engine._resolve_variant()
    fn = engine._build_program(
        "aux" if aux else "plain", 1, int(traffic["window"]), 1, True,
        telemetry, 0, codec, frac, engine.model_axes, engine.layout.name,
        False, 0.0, n, mesh_axis_size(mesh), 1, 0,
    )
    return fn, args


def report(name: str, fn, args: tuple) -> dict:
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    seconds = time.perf_counter() - t0
    text, mem = compiled.as_text(), compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)  # noqa: E731
    return {
        "workload": name, "compile_s": round(seconds, 1),
        "argument_gb": gb(mem.argument_size_in_bytes),
        "output_gb": gb(mem.output_size_in_bytes),
        "alias_gb": gb(mem.alias_size_in_bytes),
        "temp_gb": gb(mem.temp_size_in_bytes),
        "program_gb": gb(mem.generated_code_size_in_bytes),
        "total_gb": gb(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes
        ),
        "all_reduce": len(re.findall(r" all-reduce(-start)?\(", text)),
        "tpu_custom_call": text.count("tpu_custom_call"),
    }


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--nodes", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark import cells

    # A described-topology executable can be written to the persistent
    # cache but not read back without a chip: keep the cache off here.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY
    ).devices
    names = args.workloads or [
        w["name"] for w in cells.load_benchmark()["workloads"]
    ]
    print(json.dumps({
        "topology": TOPOLOGY, "device_kind": devices[0].device_kind,
        "note": "compile-only rehearsal: NOT a chip run, no times or results",
    }))
    for name in names:
        cell = cells.load_cell(name)
        fn, fn_args = window_program(cell, list(devices), args.nodes)
        print(json.dumps(report(name, fn, fn_args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
