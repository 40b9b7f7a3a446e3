"""Toy sizes of the cell ``zaya1_silo_8k`` for the CPU tests: the
cell's own files through the harness's own loader, widths and lengths
shrunk, float32 compute so the comparison with the plain reference can
be tight. Three layers (the router state passes two hand-overs), 8
query heads on 2 key heads, 4 of 8 experts held, one chosen a token;
``first`` moves the share (``first`` of ``experts_held``)."""

import dataclasses

from benchmark import cells

CELL = "zaya1_silo_8k"
TOY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "moe_intermediate_size": 16, "router_hidden_size": 12,
    "vocab_size": 64, "layers": [0, 1, 2], "num_hidden_layers": 3,
    "compute_dtype": "float32",
    "published": {"num_hidden_layers": 40, "num_experts": 8, "vocab_size": 512},
}
TOY_TRAFFIC = {"seq": 32, "batch": 2, "loss_rounds": 4}


def toy_cell(first: int = 0, count: int = 4, **config) -> cells.Cell:
    cell = cells.load_cell(CELL)
    traffic = dict(cell.traffic, **TOY_TRAFFIC)
    traffic["check"] = dict(cell.traffic["check"], seq=32, batch=2)
    rope = dict(cell.config["rope_parameters"])
    rope["hybrid"] = dict(rope["hybrid"], rope_theta=100.0)
    return dataclasses.replace(
        cell, traffic=traffic,
        config=dict(
            cell.config, **TOY_CONFIG, rope_parameters=rope,
            num_experts=count,
            experts_held={
                "first": first, "count": count, "router_width": 8,
                "experts_per_token": 1,
            },
            **config,
        ),
    )
