"""Layer "kernels": device milliseconds a round in the BANDED attention
layers (scope ``window_attention`` of ``tpfl.models.mellum.MellumBlock``:
projections, rotary positions and ``blockwise_attention`` with a
``window``, which runs the XLA block loop — the Pallas kernels have no
band), busiest device. The number a band inside the kernels must move.
Source: device trace, by named scope."""

from benchmark import scope_paths


def read(obs):
    table = scope_paths.scope_ms_per_round(obs, "window_attention")
    return None if table is None else table["window_attention"]
