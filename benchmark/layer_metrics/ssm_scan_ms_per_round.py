"""Layer "kernels": device milliseconds a round in the selective scan's
recurrence alone (scope ``ssm_scan`` of ``tpfl.parallel.selective_scan``,
forward and backward), busiest device. Source: device trace, by named
scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "ssm_scan")
    return None if table is None else table["ssm_scan"]
