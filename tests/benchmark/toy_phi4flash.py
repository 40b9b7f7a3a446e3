"""Toy sizes of the cell ``phi4flash_silo_8k`` for the CPU tests: the
cell's own files through the harness's own loader, widths and lengths
shrunk, float32 compute so the comparison with the plain reference can
be tight. ``full_pattern`` runs ALL layers of a published depth of 8
(window 8 at seq 32: the band, the memory hand-off and the shared keys
and values are all live); otherwise the cell's own kind of cut, the four
layers around the middle."""

import dataclasses

from benchmark import cells

CELL = "phi4flash_silo_8k"
TOY_CONFIG = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 4, "vocab_size": 64, "sliding_window": 8,
    "compute_dtype": "float32",
    "mamba": {"expand": 2, "d_state": 4, "d_conv": 4, "dt_rank": 2},
    "published": {"num_hidden_layers": 8, "vocab_size": 64},
}
TOY_TRAFFIC = {"seq": 32, "batch": 2, "loss_rounds": 4}


def toy_cell(full_pattern: bool = False) -> cells.Cell:
    cell = cells.load_cell(CELL)
    traffic = dict(cell.traffic, **TOY_TRAFFIC)
    traffic["check"] = dict(cell.traffic["check"], seq=32, batch=2)
    layers = list(range(8)) if full_pattern else [4, 5, 6, 7]
    return dataclasses.replace(
        cell, traffic=traffic,
        config=dict(cell.config, **TOY_CONFIG, layers=layers,
                    num_hidden_layers=len(layers)),
    )
