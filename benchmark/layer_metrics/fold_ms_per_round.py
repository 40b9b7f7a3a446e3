"""Layer "round body": device milliseconds a round under the scope
``tpfl.fold`` — fold weights, weighted sums, the ``psum`` across chips
(the ``all-reduce`` that ``collective_ms_per_round`` times lies inside
it) and the broadcast back to every node — on the busiest device.
Source: device trace, by named scope."""

from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_round(obs, "tpfl.fold")
