"""Layer "round body": device milliseconds a round in what the top-1
expert layers do AROUND the experts — the router MLP with the state it
hands to the layer above (scope ``moe_router``), the sort of the tokens
by expert and the plan (``moe_dispatch``, which in the backward also
gathers the tokens' gradients), and the gather back to tokens
(``moe_combine``) — busiest device. It scales with the row buffer's
head, not with the rows routed. ``moe_routing_ms_per_round`` under a
name of its own. Source: device trace, by named scope."""

from benchmark import scope_paths

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, *SCOPES)
    return None if table is None else sum(table.values())
