"""Layer "round body": device milliseconds a round in the mixers' work
that is neither the recurrence nor attention — the Mamba mixer around
its scan (scope ``mamba`` less ``ssm_scan``: projections, convolution,
gates) plus the gated memory unit (scope ``gmu``) — busiest device.
Source: device trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "mamba", "ssm_scan", "gmu")
    if table is None:
        return None
    return table["mamba"] - table["ssm_scan"] + table["gmu"]
