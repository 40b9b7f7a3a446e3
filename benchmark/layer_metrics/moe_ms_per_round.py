"""Layer "round body": device milliseconds a round in the expert layers
(scope ``moe`` of ``tpfl.models.mellum.MellumBlock``: router, dispatch,
the experts' grouped products, combine — forward, recomputation and
backward), busiest device. Source: device trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "moe")
    return None if table is None else table["moe"]
