"""Layer "kernels": device milliseconds a round in the experts
themselves (scope ``moe_experts`` of
``tpfl.parallel.moe.held_experts_moe``: the row gather, the grouped
products — on a TPU the Pallas kernels ``gmm`` / ``tgmm`` — and the
gates, forward, recomputation and backward), busiest device.
``moe_experts_ms_per_round`` under a name of its own. Source: device
trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "moe_experts")
    return None if table is None else table["moe_experts"]
