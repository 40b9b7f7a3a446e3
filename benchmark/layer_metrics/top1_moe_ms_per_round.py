"""Layer "round body": device milliseconds a round in the top-1 expert
layers (scope ``moe`` of ``tpfl.models.zaya.ZayaBlock``: the router MLP
and its state, dispatch, the experts' grouped products, combine —
forward, recomputation and backward), busiest device. ``moe_ms_per_round``
under a name of its own: that entry lists its cells by name. Source:
device trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "moe")
    return None if table is None else table["moe"]
