"""Toy sizes of the cell ``mellum2_silo_8k`` for the CPU tests: the
cell's own files through the harness's own loader, widths and lengths
shrunk, float32 compute so the comparison with the plain reference can
be tight. One whole period (three banded layers and the full one; window
8 at seq 32, so the band cuts), 4 of 16 experts held, 4 chosen a token;
``held`` moves the share (``first`` of ``experts_held``)."""

import dataclasses

from benchmark import cells

CELL = "mellum2_silo_8k"
TOY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "moe_intermediate_size": 16, "vocab_size": 64,
    "sliding_window": 8, "num_experts_per_tok": 4,
    "compute_dtype": "float32",
    "published": {"num_hidden_layers": 28, "num_experts": 16, "vocab_size": 256},
}
TOY_TRAFFIC = {"seq": 32, "batch": 2, "loss_rounds": 4}


def toy_cell(first: int = 0, count: int = 4) -> cells.Cell:
    cell = cells.load_cell(CELL)
    traffic = dict(cell.traffic, **TOY_TRAFFIC)
    traffic["check"] = dict(cell.traffic["check"], seq=32, batch=2)
    rope = {
        kind: dict(table, rope_theta=100.0)
        for kind, table in cell.config["rope_parameters"].items()
    }
    rope["full_attention"]["original_max_position_embeddings"] = 16
    return dataclasses.replace(
        cell, traffic=traffic,
        config=dict(
            cell.config, **TOY_CONFIG, rope_parameters=rope,
            num_experts=count,
            experts_held={
                "first": first, "count": count, "router_width": 16,
                "experts_per_token": 4,
            },
        ),
    )
