"""Toy sizes of the benchmark's cells for the CPU tests: the cell's own
files, loaded by the harness's own loader, with only sizes shrunk.
Widths the zoo fixes stay; float32 compute so that the comparison with
the plain reference can be tight."""

import dataclasses

from benchmark import cells

TOY_CONFIG = {
    "resnet18_cifar100": {
        "stage_sizes": [1, 1, 1, 1], "num_classes": 10,
        "image_size": [8, 8, 3], "compute_dtype": "float32",
    },
    "gpt2_small": {
        "n_embd": 32, "n_head": 2, "n_layer": 2, "n_positions": 16,
        "n_inner": 128, "vocab_size": 64, "compute_dtype": "float32",
    },
}
TOY_TRAFFIC = {"batch": 4, "loss_rounds": 4}


def toy_cell(name: str) -> cells.Cell:
    cell = cells.load_cell(name)
    traffic = dict(cell.traffic, **TOY_TRAFFIC)
    traffic["nodes"] = 8 if cell.traffic["mesh"] else 4
    traffic["window"] = min(2, cell.traffic["window"])
    traffic["check"] = dict(cell.traffic["check"], batch=4)
    if "seq" in traffic:
        traffic["seq"] = traffic["check"]["seq"] = 16
    return dataclasses.replace(
        cell, traffic=traffic,
        config=dict(cell.config, **TOY_CONFIG[cell.config["name"]]),
    )
