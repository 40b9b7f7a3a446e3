"""Layer "round body": device milliseconds a round under the scope
``tpfl.telemetry`` — the per-node and per-round statistics of the
telemetry carry and their writes into it — on the busiest device. A
program without ``ENGINE_TELEMETRY`` has no such scope. Source: device
trace, by named scope."""

from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_round(obs, "tpfl.telemetry")
