"""Layer "kernels": device milliseconds a round in the XLA block loop of
``blockwise_attention`` alone (scope ``block_attention``: the forward
core and the one-sweep recompute backward; no projection, no
difference or norm around it), busiest device. Source: device trace, by
named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "block_attention")
    return None if table is None else table["block_attention"]
