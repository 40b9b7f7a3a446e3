"""Layer "kernels": device milliseconds a round in differential attention
(scope ``diff_attention``: projections, the block loop of
``blockwise_attention`` and its recompute backward, the difference and
its norm — full, cross and window layers alike), busiest device. Source:
device trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "diff_attention")
    return None if table is None else table["diff_attention"]
