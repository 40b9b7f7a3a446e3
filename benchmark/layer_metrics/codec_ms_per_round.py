"""Layer "round body": device milliseconds a round under the scope
``tpfl.codec`` — the in-program wire codec's round trip over every
node's trained parameters — on the busiest device. A dense program has
no such scope. Source: device trace, by named scope."""

from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_round(obs, "tpfl.codec")
