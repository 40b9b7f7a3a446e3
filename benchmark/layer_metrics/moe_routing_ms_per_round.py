"""Layer "round body": device milliseconds a round in what the expert
layers do AROUND the experts — the router (scope ``moe_router``), the
sort of the (token, choice) pairs and the plan (``moe_dispatch``, which
in the backward also gathers the tokens' gradients), and the gather back
to tokens (``moe_combine``) — busiest device. Work a dense MLP has none
of; it scales with the row buffer's worst-case size, not with the rows
routed. Source: device trace, by named scope."""

from benchmark import scope_paths

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, *SCOPES)
    return None if table is None else sum(table.values())
