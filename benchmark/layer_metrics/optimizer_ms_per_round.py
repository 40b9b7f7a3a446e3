"""Layer "round body": device milliseconds a round under the scope
``tpfl.optimizer`` (``opt.update`` + ``apply_updates`` inside the local
training's batch step) on the busiest device. Reads ~0 where XLA fuses
the update into the weight-gradient fusions, which then carry the
training scope. Source: device trace, by named scope."""

from benchmark import program_trace


def read(obs):
    return program_trace.scope_ms_per_round(obs, "tpfl.optimizer")
